"""GLM-4.7-Flash (`models/glm4_moe_lite.py`) against its float32
reference (`benchmark/reference_glm4_moe_lite.py`), and what it asked of
`models/moe.py` (a selection bias, a weight scale) and of
`SyntheticTrial` (a learning-rate warm-up), on the CPU at a small size
with seeded random weights. Widths are divided; every mechanism is kept:
latent attention on 4 heads of 12 + 4 with the one rotary key a token,
a leading dense layer and 2 expert layers, 16 experts top-4 with 4 held,
a non-zero selection bias, the 1.8, an ungated shared expert, the MTP
module, a length the reference's query blocks do not divide.

Tolerances. The program runs here in float32 compute (`dtype`), so what
separates it from the reference is float32 rounding and the ORDER of
float32 sums (the grouped matmul against the masked dense one, one
256-wide score product against two of 192 and 64): 2e-7 to 5e-7 of a
leaf's norm, measured. `RTOL` = 5e-4 is `tests/test_qwen3_next.py`'s and
is a thousand times below what a left-out term moves (the mutation cases
below: 5e-2 and more) or bf16 accumulation would (4e-3 a sum).
"""
import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import reference_glm4_moe_lite as ref
from determined_tpu import core
from determined_tpu.exec.builtin_trials import SyntheticTrial
from determined_tpu.models import get_model, moe
from determined_tpu.trainer import Batch, Trainer

glm = importlib.import_module("determined_tpu.models.glm4_moe_lite")

RTOL = 5e-4
CELL = "glm47flash-train-8k-ep8share"
TINY = dict(
    model_type="glm4_moe_lite", vocab_size=96, hidden_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=16, kv_lora_rank=12, qk_nope_head_dim=12,
    qk_rope_head_dim=4, v_head_dim=16, rope_theta=1000000,
    rms_norm_eps=1e-5, intermediate_size=48, first_k_dense_replace=1,
    moe_intermediate_size=16, n_routed_experts=4, num_experts_routed=16,
    first_expert=4, n_shared_experts=1, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=1.8,
    num_nextn_predict_layers=1, mtp_loss_weight=0.3)
SEQ = 70      # the reference's blocks of 32 queries divide neither 70 nor 69


def _relative(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 96)


def _params(model):
    """Initial parameters, with a selection bias that is not 0 (as
    initialised it takes no part: a test could not see it dropped)."""
    params = model.init(jax.random.PRNGKey(0))
    for i, tree in enumerate((params, params.get("mtp", {}))):
        if "moe" in tree:
            tree["moe"]["bias"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(5 + i), tree["moe"]["bias"].shape)
    return params


def _program(**over):
    model = get_model("glm4-moe-lite", dtype=jnp.float32, **{**TINY, **over})
    params = _params(model)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, {"tokens": _tokens()}, None),
        has_aux=True))(params)
    return params, loss, grads, metrics


@pytest.fixture(scope="module")
def both():
    params, loss, grads, metrics = _program()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ref, "QUERY_BLOCK", 32)
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_grads = jax.jit(jax.value_and_grad(
                lambda p: ref.loss(p, _tokens(), TINY)))(params)
            terms = ref.loss_terms(params, _tokens(), TINY)
            logits = ref.forward(params, _tokens(), TINY)
    return {"loss": (loss, ref_loss), "metrics": metrics, "terms": terms,
            "logits": logits, "grads": {
                jax.tree_util.keystr(path): (g, r) for (path, g), r in zip(
                    jax.tree_util.tree_leaves_with_path(grads),
                    jax.tree.leaves(ref_grads))}}


LEAVES = sorted(
    jax.tree_util.keystr(path) for path, _ in
    jax.tree_util.tree_leaves_with_path(
        get_model("glm4-moe-lite", **TINY).logical_axes(),
        is_leaf=lambda x: isinstance(x, tuple)))


def test_loss_and_its_two_terms_are_the_references(both):
    loss, ref_loss = both["loss"]
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    main, mtp = both["terms"]
    assert float(both["metrics"]["mtp_loss"]) == pytest.approx(
        float(mtp), rel=1e-5)
    assert float(loss) == pytest.approx(float(main) + 0.3 * float(mtp),
                                        rel=1e-5)
    assert 0.3 < float(both["metrics"]["moe_held_rows_per_token"]) < 3.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_is_the_references(both, leaf):
    got, want = both["grads"][leaf]
    if leaf.endswith("['bias']"):
        # the selection bias takes part in a choice and in nothing else
        assert not np.any(np.asarray(got)) and not np.any(np.asarray(want))
        return
    assert float(jnp.max(jnp.abs(want))) > 0, "a parameter nothing reads"
    assert _relative(got, want) < RTOL


def _drop_shared_expert(mp):
    mp.setattr(moe, "shared_expert",
               lambda h, gate, w_in, w_out: jnp.zeros_like(h))


def _drop_selection_bias(mp):
    real = moe.expert_layer
    mp.setattr(moe, "expert_layer",
               lambda *a, **kw: real(*a, **{**kw, "bias": None}))


def _drop_shared_rotary_key(mp):
    mp.setattr(glm, "_shared_rotary_key", lambda k_r, heads, theta: jnp.zeros(
        (*k_r.shape[:2], heads, k_r.shape[-1]), k_r.dtype))


MUTATIONS = {
    "shared-rotary-key": _drop_shared_rotary_key,
    "q-norm": lambda mp: mp.setattr(
        glm, "_q_latent_norm", lambda c_q, w, eps: c_q),
    "selection-bias": _drop_selection_bias,
    "shared-expert": _drop_shared_expert,
    "the-1.8": {"routed_scaling_factor": 1.0},      # by configuration
    "mtp-term": {"mtp_loss_weight": 0.0},
}


@pytest.mark.parametrize("term", sorted(MUTATIONS))
def test_a_left_out_term_fails_the_comparison(monkeypatch, both, term):
    """The comparison above is tight enough to see each term: the program
    with the term dropped is off the reference by far more than RTOL in
    some gradient (the loss at initialisation hardly sees any of them)."""
    over = {}
    if isinstance(MUTATIONS[term], dict):
        over = MUTATIONS[term]
    else:
        MUTATIONS[term](monkeypatch)
    _params_, _loss, grads, _ = _program(**over)
    worst = max(
        _relative(g, both["grads"][jax.tree_util.keystr(path)][1])
        for path, g in jax.tree_util.tree_leaves_with_path(grads))
    assert worst > 100 * RTOL, (term, worst)


# -- latent attention, written out a head at a time ---------------------------
def test_mla_is_per_head_attention_with_the_rotary_key_broadcast():
    """`_mla_half` against the mixer written out head by head: each
    head's scores are its own 12-wide part plus its rotary 4 against the
    ONE rotated key of the token, over sqrt(16), causal."""
    model = get_model("glm4-moe-lite", dtype=jnp.float32, **TINY)
    c = model.config
    w = jax.tree.map(lambda a: a[1], _params(model)["attn"])
    norm = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (32,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 32))
    got = model._mla_half(x, norm, w)

    def rope(v):                                    # v [S, 4]: pairs (j, j+2)
        inv = 1.0 / c.rope_theta ** (np.arange(2) * 2 / 4)
        angle = np.arange(SEQ)[:, None] * inv
        cos, sin = np.cos(angle), np.sin(angle)
        return np.concatenate([v[:, :2] * cos - v[:, 2:] * sin,
                               v[:, 2:] * cos + v[:, :2] * sin], -1)

    def rms(v, weight):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + 1e-5) * weight

    w, norm, x = jax.tree.map(lambda a: np.asarray(a, np.float64),
                              (w, norm, x))
    want = np.zeros_like(x)
    causal = np.tril(np.ones((SEQ, SEQ), bool))
    for b in range(2):
        h = rms(x[b], norm)
        c_q = rms(h @ w["wq_a"], w["q_norm"])
        kv_a = h @ w["wkv_a"]
        c_kv, k_r = rms(kv_a[:, :12], w["kv_norm"]), rope(kv_a[:, 12:])
        y = 0.0
        for j in range(4):
            q = c_q @ w["wq_b"][:, j]
            kv = c_kv @ w["wkv_b"][:, j]
            scores = (q[:, :12] @ kv[:, :12].T + rope(q[:, 12:]) @ k_r.T) / 4.0
            scores = np.where(causal, scores, -np.inf)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            y = y + (p / p.sum(-1, keepdims=True)) @ kv[:, 12:] @ w["wo"][j]
        want[b] = x[b] + y
    assert _relative(got, jnp.asarray(want, jnp.float32)) < RTOL


# -- the expert layer's selection bias and weight scale -----------------------
def _layer_inputs(t=48, d=32, f=16, e=16):
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    return dict(
        h=jax.random.normal(ks[0], (t, d)),
        router=jax.random.normal(ks[1], (d, e)) * 0.3,
        w_in=jax.random.normal(ks[2], (e, d, 2, f)) * 0.2,
        w_out=jax.random.normal(ks[3], (e, f, d)) * 0.2,
        bias=jax.random.normal(ks[4], (e,)) * 0.4,
        shared_in=jax.random.normal(ks[5], (d, 2, f)) * 0.2,
        shared_out=jax.random.normal(ks[6], (f, d)) * 0.2)


def test_the_choice_follows_score_plus_bias_and_the_weights_the_score():
    """Written out: the experts with the largest s + b are chosen and
    weigh 1.8 s / sum of the chosen s. The bias here turns the choice
    over for most tokens (or the test would not see it)."""
    a = _layer_inputs()
    got, _ = moe.expert_layer(
        a["h"], a["router"], a["w_in"], a["w_out"], top_k=4,
        score=moe.sigmoid_scores, bias=a["bias"], scale=1.8, norm_eps=1e-20)
    h, router, bias = (np.asarray(a[k], np.float64)
                       for k in ("h", "router", "bias"))
    s = 1.0 / (1.0 + np.exp(-(h @ router)))
    chosen = np.argsort(-(s + bias), axis=-1)[:, :4]
    assert np.mean(np.sort(chosen) != np.sort(
        np.argsort(-s, axis=-1)[:, :4])) > 0.2
    want = np.zeros_like(h)
    for t in range(h.shape[0]):
        weights = 1.8 * s[t, chosen[t]] / (s[t, chosen[t]].sum() + 1e-20)
        for e, weight in zip(chosen[t], weights):
            want[t] += weight * np.asarray(
                moe.swiglu(a["h"][t:t + 1], a["w_in"][e], a["w_out"][e]))[0]
    assert _relative(got, jnp.asarray(want, jnp.float32)) < RTOL


@pytest.mark.parametrize("kw", [
    {}, {"scale": 1.0}, {"bias": None, "norm_eps": 0.0}],
    ids=["as-today", "scale-1", "no-bias"])
def test_the_new_arguments_default_to_todays_layer(kw):
    """Qwen3-Next's calls pass none of them: bias `None`, scale 1 and
    no guard trace to the program they traced to before."""
    a = _layer_inputs()
    args = (a["h"], a["router"], a["w_in"], a["w_out"])
    trace = lambda **kw: str(jax.make_jaxpr(  # noqa: E731
        lambda *args: moe.expert_layer(*args, top_k=4, **kw)[0])(*args))
    assert trace(**kw) == trace()
    assert trace(scale=1.8) != trace() != trace(bias=a["bias"])


@pytest.mark.parametrize("held", [2, 8, 16])
def test_the_shares_add_up_to_the_uncut_references_layer(held):
    """The routed parts of all 16 / `held` shares (sigmoid, bias,
    normalised, x 1.8), plus the shared expert ONCE, are the uncut
    REFERENCE's layer with all 16 held; and the shares' counters sum to
    4 rows a token."""
    a = _layer_inputs()
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
           "norm_topk_prob": True, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(
            a["h"], {k: a[k] for k in a if k != "h"}, cfg)
    parts, rows = jnp.zeros_like(whole), 0.0
    for first in range(0, 16, held):
        y, c = moe.expert_layer(
            a["h"], a["router"], a["w_in"][first:first + held],
            a["w_out"][first:first + held], top_k=4, first_expert=first,
            score=moe.sigmoid_scores, bias=a["bias"], scale=1.8,
            norm_eps=1e-20)
        parts, rows = parts + y, rows + float(c["held_rows"])
    assert rows == 48 * 4
    shared = moe.shared_expert(a["h"], None, a["shared_in"], a["shared_out"])
    assert _relative(parts + shared, whole) < RTOL


# -- the MTP module's targets -------------------------------------------------
@pytest.fixture(scope="module")
def masked_loss():
    """(loss, MTP term) of the tiny model as a function of the loss
    mask, compiled once."""
    model = get_model("glm4-moe-lite", dtype=jnp.float32, **TINY)
    params = _params(model)

    @jax.jit
    def run(mask):
        loss, metrics = model.loss(
            params, {"tokens": _tokens(), "loss_mask": mask}, None)
        return loss, metrics["mtp_loss"]

    return run


@pytest.mark.parametrize("at", [2, 37, SEQ - 1])
def test_mtp_targets_are_the_token_after_next(both, masked_loss, at):
    """With only target position `at` counted, the next-token term is
    the reference's logits at `at - 1` against token `at`, and the MTP
    term its module's logits at `at - 2` against the same token."""
    loss, mtp_loss = masked_loss(jnp.zeros((2, SEQ)).at[:, at].set(1.0))
    logits, mtp_logits = both["logits"]
    target = _tokens()[:, at]

    def nll(row):
        return jnp.mean(jax.nn.logsumexp(row, -1) - jnp.take_along_axis(
            row, target[:, None], -1)[:, 0])

    want_mtp = nll(mtp_logits[:, at - 2])
    assert float(mtp_loss) == pytest.approx(float(want_mtp), rel=1e-4)
    assert float(loss) == pytest.approx(
        float(nll(logits[:, at - 1]) + 0.3 * want_mtp), rel=1e-4)


def test_no_mtp_layers_removes_the_module_and_its_term(both):
    model = get_model("glm4-moe-lite", dtype=jnp.float32,
                      **{**TINY, "num_nextn_predict_layers": 0})
    assert "mtp" not in model.logical_axes()
    params, loss, _, metrics = _program(num_nextn_predict_layers=0)
    assert "mtp" not in params and "mtp_loss" not in metrics
    # same key, same leaves before `mtp` in the tree: the trunk is `both`'s
    assert float(loss) == pytest.approx(float(both["terms"][0]), rel=1e-5)


# -- through the registry, the trainer and the benchmark's driver ------------
def test_trainer_fit_through_the_registry_name(tmp_path):
    """`Trainer.fit` of `get_model("glm4-moe-lite", ...)` as of any
    registry model: SyntheticTrial's token batches by the model's input
    contract, the default mesh (8 virtual devices: each batch shard
    routes its own tokens), finite losses, the counters in every report."""
    reports = []

    class Context(core._train.DummyTrainContext):
        def _report(self, group, steps_completed, metrics):
            if group == "training":
                reports.append(metrics)

    trial = SyntheticTrial({
        "model": "glm4-moe-lite", "model_kw": dict(TINY), "seq_len": 32,
        "vocab_size": 96, "batch_size": 8, "lr": 1e-2,
        "lr_warmup_steps": 4})
    ctx = core._context._dummy_init(checkpoint_storage=str(tmp_path))
    ctx.train = Context()
    trainer = Trainer(trial, ctx)
    trainer.fit(max_length=Batch(6), report_period=Batch(2))
    assert len(reports) == 3
    # (uniform random tokens: ln 96 = 4.56 a term, 1.3 of them)
    assert all(5.5 < r["loss"] < 6.5 for r in reports)
    assert all(4.0 < r["mtp_loss"] < 5.0 for r in reports)
    assert 0.2 < reports[0]["moe_held_rows_per_token"] < 3.0
    assert reports[0]["moe_load_max_over_mean"] >= 1.0
    # AdamW leaves the selection bias where it was initialised
    state = jax.device_get(trainer.state["params"])
    assert not np.any(state["moe"]["bias"])
    assert not np.any(state["mtp"]["moe"]["bias"])


def test_unsupported_public_keys_are_refused():
    for key, value in [("topk_method", "greedy"), ("n_group", 8),
                       ("tie_word_embeddings", True),
                       ("rope_scaling", {"type": "yarn"})]:
        with pytest.raises(ValueError, match=key):
            get_model("glm4-moe-lite", **{**TINY, key: value})
    with pytest.raises(ValueError, match="grouped heads"):
        get_model("glm4-moe-lite", **{**TINY, "num_key_value_heads": 2})
    with pytest.raises(ValueError, match="unequal width"):
        get_model("glm4-moe-lite", **{**TINY, "v_head_dim": 8})
    with pytest.raises(ValueError, match="routed over"):
        get_model("glm4-moe-lite", **{**TINY, "first_expert": 14})
    with pytest.raises(ValueError, match="one MTP module"):
        get_model("glm4-moe-lite", **{**TINY, "num_nextn_predict_layers": 2})
    config = glm.Glm4MoeLiteConfig.from_keys(
        {**TINY, "max_position_embeddings": 202752})
    assert dataclasses.asdict(config)["num_experts_routed"] == 16


def test_rehearsal_of_the_cell_off_the_chip():
    """`benchmark/tests/rehearse_lm_models.py`: the cell's own driver,
    harness and readers end to end at a tiny size, traced; `correct`
    against the reference, no compilation in the window, the counters in
    the line. (One case: every further one is three more compilations
    beside the suite's timing-sensitive tests.)"""
    from benchmark.tests import rehearse_lm_models

    result = rehearse_lm_models.rehearse(
        CELL, seed=3_000_000_000, seconds=1.0, trace=True)
    assert result["correct"] and result["failed"] == 0
    assert 0.2 < result["metrics"]["moe_held_rows_per_token"]["value"] < 3
    assert "mla_train_mfu" in result["metrics"]
    assert result["metrics"]["train_step_ms"]["value"] > 0


# -- the learning rate's warm-up ----------------------------------------------
def _optimizer(**hparams):
    return SyntheticTrial({"model": "gpt-tiny", **hparams}).build_optimizer()


@pytest.mark.parametrize("step,share", [
    (1, 1 / 2000), (2, 2 / 2000), (1000, 0.5), (2000, 1.0), (5000, 1.0)])
def test_the_ramp_reaches_lr_at_step_n(step, share):
    """Step n's update runs at lr n / N up to N, at lr after: the update
    the warmed-up optimizer makes at step n is that share of the one the
    constant-rate optimizer makes there (fresh Adam states with their
    step counts moved to n - 1; the same gradient)."""
    params, grads = {"w": jnp.zeros((3,))}, {"w": jnp.full((3,), 1e-4)}
    count = jnp.asarray(step - 1, jnp.int32)
    updates = []
    for tx in (_optimizer(lr=1e-3, lr_warmup_steps=2000),
               _optimizer(lr=1e-3)):
        state = jax.tree.map(
            lambda a: count if a.dtype == jnp.int32 and a.ndim == 0 else a,
            tx.init(params))
        updates.append(tx.update(grads, state, params)[0]["w"])
    assert float(jnp.max(jnp.abs(updates[1]))) > 0
    np.testing.assert_allclose(updates[0], share * updates[1], rtol=1e-5)


def test_no_warm_up_is_the_optimizer_and_the_step_of_before(tmp_path):
    """`lr_warmup_steps` 0 (or absent) builds `optax.adamw(lr)` behind
    the clip, with no schedule in its state, and the train step lowers to
    the text it lowered to without the key (`tests/test_tpu_compile.py`'s
    way: the program, not its results)."""
    assert jax.tree.structure(_optimizer(lr_warmup_steps=0).init(
        {"w": jnp.zeros(3)})) == jax.tree.structure(optax.chain(
            optax.clip_by_global_norm(1.0), optax.adamw(1e-3)).init(
                {"w": jnp.zeros(3)}))

    def lowered(**hparams):
        trial = SyntheticTrial({
            "model": "gpt-tiny", "seq_len": 32, "vocab_size": 256,
            "batch_size": 8, **hparams})
        trainer = Trainer(trial, core._context._dummy_init(
            checkpoint_storage=str(tmp_path)))
        batch = trainer._put_batch(next(trial.build_training_data()))
        return trainer._build_step_fn().lower(
            trainer.state, batch, np.float32(1.0), trainer._zero_skips()
        ).as_text()

    assert lowered(lr_warmup_steps=0) == lowered()
    assert lowered(lr_warmup_steps=7) != lowered()


# -- the configuration's file and the benchmark's counts ---------------------
def _cell():
    from benchmark.run import Cell

    return Cell(CELL)


def test_configuration_keeps_every_published_width():
    cell = _cell()
    c = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(r for r in map(json.loads, f)
                         if r["name"] == "GLM-4.7-Flash")
    assert c["source"] == published["source_url"]
    differs = {k for k, v in published["config"].items() if c.get(k) != v}
    assert differs <= set(c["reduced"]), differs
    assert c["published"] == {k: published["config"][k] for k in (
        "num_hidden_layers", "n_routed_experts", "vocab_size")}
    assert (c["n_routed_experts"], c["vocab_size"]) == (8, 19360)
    assert c["num_experts_routed"] == 64 and c["num_experts_per_tok"] == 4
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] == 4
    for key in ("weights", "rotary", "selection_bias", "mtp_loss_weight",
                "mtp_shares", "aux_loss"):
        assert key in c["assumed"], key
    assert "8 chips share" in c["deployment"]
    t = cell.traffic
    assert t["kind"] == "train_lm_models" and t["seq_len"] == 8192
    assert (t["lr"], t["lr_warmup_steps"]) == (0.001, 2000)
    assert isinstance(t["weights_seed"], int) and t["mesh"] == {"data": 1}


def test_the_cut_holds_its_parameters():
    """ISSUE 35's count: MLA 21.76 M a block; the dense layer 84.67 M; an
    expert layer 106.82 M; embedding and head 79.30 M: 591.3 M, and with
    the MTP module (a block and W_eh, 8.39 M) 706.5 M."""
    from benchmark.drivers import train_lm_models

    with train_lm_models.as_train_lm():
        name, kw, _ = train_lm_models.train_lm.model_of(_cell().config)
    totals = {}
    for mtp in (0, 1):
        shapes = jax.eval_shape(
            get_model(name, **{**kw, "num_nextn_predict_layers": mtp}).init,
            jax.random.PRNGKey(0))
        sizes = jax.tree.map(lambda a: a.size, shapes)
        totals[mtp] = sum(jax.tree.leaves(sizes))
    assert abs(totals[0] / 1e6 - 591.3) < 0.05, totals
    assert abs(totals[1] / 1e6 - 706.5) < 0.05, totals
    assert kw["num_nextn_predict_layers"] in (0, 1)
    mla = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
           + 5120 * 2048)
    assert sum(jax.tree.leaves(sizes["attn"])) == 5 * (mla + 768 + 512)
    assert sum(jax.tree.leaves(sizes["moe"])) == 4 * (
        2048 * 64 + 64 + 9 * 3 * 2048 * 1536)
    assert sizes["mtp"]["eh_proj"] == 4096 * 2048
    assert sizes["tok_embed"] + sizes["head"] == 2 * 19360 * 2048


def _chip_readings():
    with open(os.path.join(os.path.dirname(ref.__file__), "tests",
                           "glm_control_readings.json")) as f:
        return [(r["seed"], which, r[which]) for r in json.load(f)["readings"]
                for which in ("program", "control")]


@pytest.mark.parametrize(
    "seed,which,got", _chip_readings(),
    ids=[f"{which}-{seed}" for seed, which, _ in _chip_readings()])
def test_the_limit_lies_between_the_chips_two_readings(seed, which, got):
    """`lm_control.py`'s readings on the chip at the timed sizes, kept as
    data: `check` passes the program's and refuses the control's, by the
    gradients and not by the loss (a limit moved past either reading
    fails here), in the routed leaves: the others read 0.013-0.06 in the
    program and 0.07-0.24 in the control, under the limit both."""
    verdict = ref.check(got["loss"], got["reference_loss"],
                        got["gradient_gaps"])
    assert verdict["ok"] is (which == "program"), verdict
    assert verdict["gap"] < ref.LOSS_TOLERANCE / 5
    room = verdict["gradient_gap"] / ref.GRADIENT_TOLERANCE
    assert room < 0.7 if which == "program" else room > 1.5
    over = [k for k, v in got["gradient_gaps"].items()
            if v > ref.GRADIENT_TOLERANCE]
    assert len(over) == (0 if which == "program" else 6), over


def test_gradient_gaps_are_a_leafs_over_all_its_layers():
    """`unstack` gives the program's tree a block an entry (the MTP
    module's under its own name); a gap is over a leaf's layers together,
    whichever form the two trees come in; a leaf whose reference
    gradient is zero reads 0 only if the program's is zero too; and
    `check` fails on a gap that is not finite or over its limit."""
    model = get_model("glm4-moe-lite", **TINY)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    want = jax.tree.map(
        lambda a: rng.normal(size=a.shape).astype(np.float32), shapes)
    want["moe"]["bias"][:] = 0.0
    want["mtp"]["moe"]["bias"][:] = 0.0
    flat = ref.unstack(want)
    assert [sorted(layer) for layer in flat["layers"]] == [
        ["attn", "dense", "norm1", "norm2"]] + 2 * [
        ["attn", "moe", "norm1", "norm2"]]
    assert [sorted(layer) for layer in flat["mtp"]["layers"]] == [
        ["attn", "moe", "norm1", "norm2"]]
    assert flat["layers"][2]["moe"]["w_in"].base is want["moe"]["w_in"]
    got = jax.tree.map(lambda a: 1.5 * a, want)
    got["attn"]["wo"] = want["attn"]["wo"].copy()
    got["attn"]["wo"][1] = 0.0                  # one layer of three dropped
    got["mtp"]["moe"]["bias"] = got["mtp"]["moe"]["bias"] + 1.0
    for form in (got, ref.unstack(got)):
        gaps = ref.gradient_gaps(form, flat)
        assert sorted(gaps) == LEAVES
        assert gaps["['moe']['w_in']"] == pytest.approx(0.5, rel=1e-5)
        assert gaps["['attn']['wo']"] == pytest.approx(3 ** -0.5, rel=0.05)
        assert gaps["['moe']['bias']"] == 0.0
        assert gaps["['mtp']['moe']['bias']"] == float("inf")
    assert not ref.check(9.0, 9.0, gaps)["ok"]
    fine = {k: 0.01 for k in gaps}
    assert ref.check(9.0, 9.0, fine)["ok"]
    assert not ref.check(9.0, 9.0 + 2 * ref.LOSS_TOLERANCE, fine)["ok"]
    over = 1.5 * ref.GRADIENT_TOLERANCE
    assert not ref.check(9.0, 9.0, {**fine, "['moe']['router']": over})["ok"]
    assert not ref.check(
        9.0, 9.0, {**fine, "['norm_f']": float("nan")})["ok"]


def test_flop_count_by_the_rows_routed():
    """Hand-worked at the published widths (ISSUE 35): an MLA mixer
    multiplies a token by 21.76 M weights, the dense FFN by 62.91 M, an
    expert layer by 0.13 + 9.44 M + rows x 9.44 M, the head by 39.65 M a
    pass, W_eh by 8.39 M; attention is 12 x 20 x 256 x S a layer."""
    from benchmark import mla_flops_bytes as fb

    c = dict(_cell().config)
    mla = (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048)
    assert fb.mla_params(c) == mla == 21757952
    expert = 3 * 2048 * 1536

    def want(rows, mtp):
        blocks = 5 + mtp
        return (blocks * mla + 3 * 2048 * 10240
                + (4 + mtp) * (2048 * 64 + expert + rows * expert)
                + mtp * 4096 * 2048 + (1 + mtp) * 2048 * 19360)

    for mtp in (0, 1):
        c["num_nextn_predict_layers"] = mtp
        for rows in (0.0, 0.5, 4.0):
            assert fb.matmul_params_per_token(c, rows) == pytest.approx(
                want(rows, mtp))
        per_token = fb.train_flops_per_token(c, 8192, 0.5)
        assert per_token == pytest.approx(
            6 * want(0.5, mtp) + 12 * (5 + mtp) * 5120 * 8192)
        # ISSUE 35's reckoning: 4.13 GFLOP a token without the module,
        # 5.14 with it
        assert per_token / 1e9 == pytest.approx((4.13, 5.14)[mtp], abs=0.006)
        flops, nbytes = fb.experts_step(c, 8192, 0.5)
        assert flops == pytest.approx((4 + mtp) * 4096 * 18 * 2048 * 1536)
        assert nbytes == (4 + mtp) * (
            8 * expert * 2 * 3 + 4096 * (2 * 2048 + 3 * 1536) * 2 * 3)
