"""Qwen3-Next (`models/qwen3_next.py`) against its float32 reference
(`benchmark/reference_qwen3_next.py`), and the two operations it brought
(`ops/gated_delta.py`, `ops/grouped_matmul.py`) against their plain
forms, on the CPU at a small size with seeded random weights. Widths are
divided; every mechanism is kept: a 3 + 1 period, 16 experts top-4 with
4 held, rotary on a quarter of the head, 2 value heads a key head, a
length that is no multiple of the chunk.

Tolerances. The program runs here in float32 compute (`dtype`), so what
separates it from the reference is float32 rounding and the ORDER of
float32 sums (the chunked rule against the token-by-token one, the
grouped matmul against the masked dense one): 1e-6 to 5e-5 relative to
a tensor's largest entry, measured. `RTOL` = 5e-4 leaves ten times that
and is a thousand times below what a left-out term moves (the mutation
cases below: 1e-1 and more) or bf16 accumulation would (4e-3 a sum).
"""
import dataclasses
import functools
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_qwen3_next as ref
from determined_tpu import core
from determined_tpu.exec.builtin_trials import SyntheticTrial
from determined_tpu.models import get_model, moe
from determined_tpu.ops import gated_delta as gd
from determined_tpu.ops import grouped_matmul as gm
from determined_tpu.trainer import Batch, Trainer

qn = importlib.import_module("determined_tpu.models.qwen3_next")

RTOL = 5e-4
TINY = dict(
    model_type="qwen3_next", vocab_size=96, hidden_size=32,
    num_hidden_layers=4, full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
    rope_theta=10000000, rms_norm_eps=1e-6, linear_conv_kernel_dim=4,
    linear_key_head_dim=8, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=8, num_experts=4, num_experts_routed=16,
    first_expert=4, num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, norm_topk_prob=True)
SEQ = 70      # one chunk of 64 and a tail of 6


def _relative(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 96)


def _program(**over):
    model = get_model("qwen3-next", dtype=jnp.float32, **{**TINY, **over})
    params = model.init(jax.random.PRNGKey(0))
    tokens = _tokens()
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, {"tokens": tokens}, None), has_aux=True))(
            params)
    return params, loss, grads, metrics


@pytest.fixture(scope="module")
def both():
    params, loss, grads, metrics = _program()
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, _tokens(), TINY)))(params)
    return {"loss": (loss, ref_loss), "metrics": metrics, "grads": {
        jax.tree_util.keystr(path): (g, r) for (path, g), r in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(ref_grads))}}


LEAVES = sorted(
    jax.tree_util.keystr(path) for path, _ in
    jax.tree_util.tree_leaves_with_path(
        get_model("qwen3-next", **TINY).logical_axes(),
        is_leaf=lambda x: isinstance(x, tuple)))


def test_loss_is_the_references(both):
    loss, ref_loss = both["loss"]
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    assert 0.3 < float(both["metrics"]["moe_held_rows_per_token"]) < 3.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_is_the_references(both, leaf):
    got, want = both["grads"][leaf]
    assert float(jnp.max(jnp.abs(want))) > 0, "a parameter nothing reads"
    assert _relative(got, want) < RTOL


def _drop_shared_gate(monkeypatch):
    real = moe.shared_expert
    monkeypatch.setattr(moe, "shared_expert",
                        lambda h, gate, w_in, w_out: real(h, None, w_in, w_out))


MUTATIONS = {
    "output-gate": lambda mp: mp.setattr(qn, "_output_gate", lambda o, g: o),
    "beta": lambda mp: mp.setattr(qn, "_write_strength", jnp.ones_like),
    "shared-gate": _drop_shared_gate,
    "top-k-normalisation": None,         # by configuration, below
}


@pytest.mark.parametrize("term", sorted(MUTATIONS))
def test_a_left_out_term_fails_the_comparison(monkeypatch, both, term):
    """The comparison above is tight enough to see each term: the program
    with the term dropped is off the reference by far more than RTOL in
    some gradient (the loss at initialisation hardly sees any of them)."""
    over = {}
    if MUTATIONS[term] is None:
        over = {"norm_topk_prob": False}
    else:
        MUTATIONS[term](monkeypatch)
    _params, _loss, grads, _ = _program(**over)
    worst = max(
        _relative(g, both["grads"][jax.tree_util.keystr(path)][1])
        for path, g in jax.tree_util.tree_leaves_with_path(grads))
    assert worst > 100 * RTOL, (term, worst)


# -- the chunked gated delta rule against the recurrence ---------------------
def _rule_inputs(s, hk=2, r=2, dk=16, dv=8, b=2):
    ks = jax.random.split(jax.random.PRNGKey(s), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, hk, dk)))
    v = jax.random.normal(ks[2], (b, s, hk * r, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, hk * r))) * jnp.exp(
        2.0 * jax.random.uniform(ks[4], (hk * r,)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, s, hk * r)))
    return q, k, v, g, beta


WHAT = ["o", "dq", "dk", "dv", "dg", "dbeta"]
#: (seq, chunk): 150 = 2 x 64 + 22; one chunk exactly; two, so that the
#: state crosses a boundary with nothing padded; 37 = 2 x 16 + 5; 5 < one
#: chunk; and the cell's heads (16 key heads of 128, 2 value heads each)
#: over two chunks of the cell's 128.
RULE_CASES = [(150, 64), (64, 64), (128, 64), (37, 16), (5, 64), (256, 128)]
_CELL_HEADS = dict(hk=16, r=2, dk=128, dv=128, b=1)


def _rule_case(seq, chunk):
    args = _rule_inputs(
        seq, **(_CELL_HEADS if (seq, chunk) == RULE_CASES[-1] else {}))
    return args, jax.random.normal(jax.random.PRNGKey(9), args[2].shape)


def _stepwise(q, k, *rest):
    """The reference's recurrence, a token at a time: a sequence a call,
    each key head repeated for the value heads it serves."""
    r = rest[0].shape[2] // q.shape[2]
    return jax.vmap(ref.delta_rule)(
        jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), *rest)


def _o_and_gradients(f, args, weight):
    grads = jax.grad(lambda *a: jnp.sum(f(*a) * weight),
                     argnums=(0, 1, 2, 3, 4))(*args)
    return dict(zip(WHAT, (f(*args),) + grads))


@functools.lru_cache(maxsize=None)
def _rule_outputs(seq, chunk, path):
    """o and the five gradients of one case: by the token-by-token
    reference (`path` None), or by the chunked rule with its recurrence
    as the `lax.scan` or as the Pallas kernels (interpreted here)."""
    args, weight = _rule_case(seq, chunk)
    if path is None:
        return _o_and_gradients(_stepwise, args, weight)
    chunked = functools.partial(
        gd.gated_delta_chunked, chunk=chunk, interpret=path == "kernel")
    return _o_and_gradients(chunked, args, weight)


@pytest.mark.parametrize("path", ["scan", "kernel"])
@pytest.mark.parametrize("seq,chunk", RULE_CASES)
@pytest.mark.parametrize("what", WHAT)
def test_chunked_rule_is_the_recurrence(seq, chunk, what, path):
    """Forward and every gradient, at lengths that are and are not
    multiples of the chunk, with the recurrence over chunks as the scan
    and as the kernel pair. Float32 sums in another order: 1e-5
    relative, measured; RTOL."""
    got = _rule_outputs(seq, chunk, path)[what]
    want = _rule_outputs(seq, chunk, None)[what]
    assert got.shape == want.shape
    assert _relative(got, want) < RTOL


@pytest.mark.parametrize("seq,chunk", RULE_CASES)
def test_rule_kernels_are_the_scan(seq, chunk):
    """The kernels run `_recurrence_step` on the blocks the scan runs it
    on, and their backward is jax's of that function: here, where both
    compute in float32, nothing separates the two but the order of the
    sums inside a product."""
    for what in WHAT:
        got = _rule_outputs(seq, chunk, "kernel")[what]
        want = _rule_outputs(seq, chunk, "scan")[what]
        assert _relative(got, want) < 1e-5, what


def test_rule_kernels_initialise_their_scratch(monkeypatch):
    """The state and its cotangent live in VMEM scratch that the chip
    hands out as the last kernel left it: both kernels zero theirs at a
    head block's first step. Two heads a program makes four head blocks
    share one scratch buffer here, two different inputs go through one
    compiled pair, and the interpreter fills what was never written with
    NaN: the second call's results are a fresh scan's."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(gd, "_HEADS_A_PROGRAM", 2)
    poison = pltpu.InterpretParams(uninitialized_memory="nan")
    weight = _rule_case(150, 64)[1]

    def both(interpret, *args):
        return _o_and_gradients(functools.partial(
            gd.gated_delta_chunked, chunk=64, interpret=interpret),
            args, weight)

    compiled = jax.jit(functools.partial(both, poison))
    compiled(*_rule_inputs(150))
    q, k, v, g, beta = _rule_inputs(150)
    second = (k * 16 ** -0.5, q * 16 ** 0.5, -v, g[::-1], beta[::-1])
    got, want = compiled(*second), both(False, *second)
    for what in WHAT:
        assert np.isfinite(got[what]).all(), what
        assert _relative(got[what], want[what]) < 1e-5, what


def test_rule_takes_the_kernels_where_the_backend_is_a_tpu(monkeypatch):
    """One path by what the code observes: the kernels on a TPU at widths
    the lanes hold whole, the scan elsewhere (here) and at other widths."""
    fa = importlib.import_module("determined_tpu.ops.flash_attention")
    cell, small = _rule_inputs(128, **_CELL_HEADS), _rule_inputs(128)
    has_kernel = lambda args: "pallas_call" in str(  # noqa: E731
        jax.make_jaxpr(lambda *a: gd.gated_delta_chunked(*a))(*args))
    assert not has_kernel(cell)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    assert has_kernel(cell) and not has_kernel(small)


@pytest.mark.parametrize("c", [16, 64, 128, 96, 7])
def test_unit_lower_inverse_and_its_gradient(c):
    """By squarings (16, 7), by halves down to 32 (64, 128), and halves
    that stop at an odd width (96 -> 48 -> 24)."""
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, c, c)), -1) * 0.2
    eye = jnp.eye(c)
    t = gd._unit_lower_inverse(a)
    np.testing.assert_allclose(
        jnp.matmul(eye + a, t), jnp.broadcast_to(eye, a.shape), atol=1e-4)
    w = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    got = jax.grad(lambda x: jnp.sum(gd._unit_lower_inverse(x) * w))(a)
    want = jax.grad(lambda x: jnp.sum(jnp.linalg.inv(eye + jnp.tril(x, -1)) * w))(a)
    assert _relative(got, want) < RTOL


# -- the grouped matmul against a loop over the experts ----------------------
SIZES = {
    "uneven-one-empty": [5, 0, 17, 1, 9],
    "rows-past-the-last-group": [3, 0, 0, 4, 2],
    "one-expert-has-all": [0, 0, 40, 0, 0],
}


@pytest.mark.parametrize("load", sorted(SIZES))
@pytest.mark.parametrize("what", ["y", "dx", "dw"])
def test_grouped_matmul_is_the_loop_over_experts(load, what):
    sizes = jnp.asarray(SIZES[load], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 24))
    w = jax.random.normal(jax.random.PRNGKey(1), (5, 24, 12))
    weight = jax.random.normal(jax.random.PRNGKey(2), (40, 12))
    n = int(sizes.sum())     # rows past the groups are undefined: not read
    if what == "y":
        got, want = (f(x, w, sizes)[:n] for f in (
            gm.grouped_matmul, gm.grouped_matmul_loop))
    else:
        i = ["dx", "dw"].index(what)
        got, want = (jax.grad(
            lambda x, w: jnp.sum((f(x, w, sizes) * weight)[:n]),
            argnums=i)(x, w)
            for f in (gm.grouped_matmul, gm.grouped_matmul_loop))
        if what == "dx":
            got, want = got[:n], want[:n]
    assert _relative(got, want) < RTOL


# -- the two row permutations: slabs of the live rows -------------------------
PERM_T, PERM_K, PERM_D, PERM_SLAB = 12, 3, 5, 8     # 36 rows, slabs of 8


def _routed(n_live):
    """`expert_layer`'s own structure: `n_live` of the k*T assignments
    go to one of 4 experts held, and those sort first."""
    kt = PERM_T * PERM_K
    live = jnp.zeros(kt, bool).at[jax.random.permutation(
        jax.random.PRNGKey(0), kt)[:n_live]].set(True)
    expert = jax.random.randint(jax.random.PRNGKey(4), (kt,), 0, 4)
    order = jnp.argsort(jnp.where(live, expert, 4), stable=True).astype(
        jnp.int32)
    return order, jnp.argsort(order).astype(jnp.int32), live


@pytest.mark.parametrize("n_live", [
    0, 1, PERM_SLAB - 1, PERM_SLAB, PERM_SLAB + 1, PERM_T * PERM_K])
def test_row_permutations_are_each_others_transpose(monkeypatch, n_live):
    """Either permutation touches the sorted positions below `n_live`
    and nothing else: what lies at or past it (uninitialised rows on the
    way in, undefined ones on the way out) is NaN here and reaches
    neither a result nor a gradient, whatever `n_live` is to the slab
    (none, a part of one, one to the row, one and a row, the whole
    buffer, which 8 does not divide)."""
    t, k, d = PERM_T, PERM_K, PERM_D
    monkeypatch.setattr(gm, "slab_rows", lambda k, t: PERM_SLAB)
    monkeypatch.setattr(
        gm.lax, "empty", lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    order, inverse, live = _routed(n_live)
    n = jnp.int32(n_live)
    h = jax.random.normal(jax.random.PRNGKey(1), (t, d))
    y = jax.random.normal(jax.random.PRNGKey(2), (t * k, d))
    dead = jnp.arange(t * k) >= n_live          # by sorted position
    poisoned = jnp.where(dead[:, None], jnp.nan, y)

    rows, back_of_rows = jax.vjp(
        lambda h: gm.rows_of_tokens(h, order, n, k), h)
    np.testing.assert_array_equal(rows[:n_live], h[order % t][:n_live])
    # the form this one replaced: every row through the inverse
    # permutation, widened, the dead ones masked, k slabs of [T, D] added
    want = jnp.sum(jnp.where(live[:, None], y[inverse], 0.0).reshape(
        k, t, d), axis=0)
    back, rows_of_back = jax.vjp(
        lambda y: gm.tokens_of_rows(y, order, n, k), poisoned)
    np.testing.assert_allclose(back, want, rtol=1e-6, atol=1e-6)
    # <rows_of_tokens(h), y> == <h, tokens_of_rows(y)> over the live rows
    np.testing.assert_allclose(
        jnp.sum(rows[:n_live] * y[:n_live]), jnp.sum(h * back),
        rtol=1e-4, atol=1e-4)
    # and the gradients: each one's is the other, of a poisoned cotangent
    (d_h,) = back_of_rows(poisoned)
    np.testing.assert_allclose(d_h, want, rtol=1e-6, atol=1e-6)
    (d_y,) = rows_of_back(h)
    np.testing.assert_array_equal(d_y[:n_live], h[order % t][:n_live])


def test_expert_layer_reads_nothing_of_the_uninitialised_rows(monkeypatch):
    """The layer itself with its rows buffer allocated full of NaN and
    slabs short enough to leave most of it so: the loss and every
    gradient are the ones a zero-filled buffer gives."""
    t, d, f, e = 48, 32, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    at = (jax.random.normal(ks[0], (t, d)), jax.random.normal(ks[1], (d, e)),
          jax.random.normal(ks[2], (4, d, 2, f)) * 0.2,
          jax.random.normal(ks[3], (4, f, d)) * 0.2)
    w = jax.random.normal(ks[4], (t, d))
    monkeypatch.setattr(gm, "slab_rows", lambda k, t: 32)

    def loss(h, router, w_in, w_out):
        y, c = moe.expert_layer(h, router, w_in, w_out, top_k=4,
                                first_expert=8)
        return jnp.sum(y * w), c["held_rows"]

    def run():
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(
            *at)

    (clean, rows), clean_grads = run()
    assert 0 < float(rows) < 4 * t - 32, "no row would be left poisoned"
    monkeypatch.setattr(
        gm.lax, "empty", lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    (got, _), grads = run()
    assert np.isfinite(float(got)) and float(got) == float(clean)
    for name, g, want in zip(("h", "router", "w_in", "w_out"),
                             grads, clean_grads):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_array_equal(g, want, err_msg=name)


# -- the shares add up to the uncut layer ------------------------------------
@pytest.mark.parametrize("top_k,normalize,held", [
    (4, True, 4), (4, False, 4), (2, True, 8), (6, True, 2)])
def test_the_shares_add_up_to_the_uncut_layer(top_k, normalize, held):
    """The routed parts of all 16 / `held` shares, plus the shared expert
    ONCE, are the uncut layer (all 16 held); and the shares' counters sum
    to top_k rows a token."""
    t, d, f, e = 48, 32, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    h = jax.random.normal(ks[0], (t, d))
    router = jax.random.normal(ks[1], (d, e))
    w_in = jax.random.normal(ks[2], (e, d, 2, f)) * 0.2
    w_out = jax.random.normal(ks[3], (e, f, d)) * 0.2
    shared = moe.shared_expert(
        h, jax.random.normal(ks[4], (d,)),
        jax.random.normal(ks[5], (d, 2, f)) * 0.2,
        jax.random.normal(ks[6], (f, d)) * 0.2)
    how = dict(top_k=top_k, normalize=normalize)
    whole, counters = moe.expert_layer(h, router, w_in, w_out, **how)
    assert float(counters["held_rows"]) == t * top_k
    parts, rows = jnp.zeros_like(whole), 0.0
    for first in range(0, e, held):
        y, c = moe.expert_layer(
            h, router, w_in[first:first + held], w_out[first:first + held],
            first_expert=first, **how)
        parts, rows = parts + y, rows + float(c["held_rows"])
    assert rows == t * top_k
    assert _relative(parts + shared, whole + shared) < RTOL


def test_expert_layer_counts_its_rows_and_its_load():
    t, d, f = 64, 8, 4
    h = jnp.ones((t, d))
    router = jnp.zeros((d, 8)).at[:, 5].set(1.0).at[:, 2].set(0.5)
    _y, c = moe.expert_layer(
        h, router, jnp.ones((4, d, 2, f)), jnp.ones((4, f, d)),
        top_k=2, first_expert=4)     # every token picks 5 (held) and 2 (not)
    assert float(c["held_rows"]) == t
    assert float(c["load_max_over_mean"]) == 4.0   # one of four has them all


# -- through the registry, the trainer and the benchmark's driver ------------
def test_trainer_fit_through_the_registry_name(tmp_path):
    """`Trainer.fit` of `get_model("qwen3-next", ...)` as of any registry
    model: SyntheticTrial's token batches by the model's input contract,
    the default mesh (8 virtual devices: each batch shard routes its own
    tokens), finite losses, the expert layer's counters in every report."""
    reports = []

    class Context(core._train.DummyTrainContext):
        def _report(self, group, steps_completed, metrics):
            if group == "training":
                reports.append(metrics)

    trial = SyntheticTrial({
        "model": "qwen3-next", "model_kw": dict(TINY), "seq_len": 32,
        "vocab_size": 96, "batch_size": 8, "lr": 1e-2})
    ctx = core._context._dummy_init(checkpoint_storage=str(tmp_path))
    ctx.train = Context()
    Trainer(trial, ctx).fit(max_length=Batch(6), report_period=Batch(2))
    assert len(reports) == 3
    # (uniform random tokens: nothing to learn beyond ln 96 = 4.56)
    assert all(4.0 < r["loss"] < 5.0 for r in reports)
    assert 0.2 < reports[0]["moe_held_rows_per_token"] < 3.0
    assert reports[0]["moe_load_max_over_mean"] >= 1.0


@pytest.mark.parametrize("contract,model", [
    ("tokens", "gpt-tiny"), ("tokens", "qwen3-next"),
    ((28, 28, 1), "mnist-mlp"), ((32, 32, 3), "cifar-cnn")])
def test_synthetic_batches_follow_the_models_input_contract(contract, model):
    kw = dict(TINY) if model == "qwen3-next" else {}
    trial = SyntheticTrial({"model": model, "model_kw": kw, "batch_size": 4,
                            "seq_len": 16, "vocab_size": 50})
    batch = next(trial._batches(0))
    if contract == "tokens":
        assert batch["tokens"].shape == (4, 16) and batch["tokens"].max() < 50
    else:
        assert batch["image"].shape == (4, *contract)


def test_unsupported_public_keys_are_refused():
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        get_model("qwen3-next", **{**TINY, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="whole periods"):
        get_model("qwen3-next", **{**TINY, "num_hidden_layers": 5})
    with pytest.raises(ValueError, match="routed over"):
        get_model("qwen3-next", **{**TINY, "first_expert": 14})
    config = qn.Qwen3NextConfig.from_keys({**TINY, "intermediate_size": 5120})
    assert dataclasses.asdict(config)["num_experts_routed"] == 16


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_off_the_chip(trace):
    """`benchmark/tests/rehearse_lm.py`: the cell's own driver, harness
    and readers end to end at a tiny size; `correct` against the
    reference, no compilation in the window, the counters in the line."""
    from benchmark.tests import rehearse_lm

    result = rehearse_lm.rehearse(
        "qwen3next-train-8k-ep16share", seed=3_000_000_000, seconds=1.0,
        trace=bool(trace))
    assert result["correct"] and result["failed"] == 0
    if trace:
        assert 0.2 < result["metrics"]["moe_held_rows_per_token"]["value"] < 3
        assert "lm_train_mfu" in result["metrics"]
    else:
        assert result["metrics"]["train_tokens_per_s"]["value"] > 0


# -- the configuration's file and the benchmark's counts ---------------------
def _cell_config():
    from benchmark.run import Cell

    return Cell("qwen3next-train-8k-ep16share")


@pytest.fixture(scope="module")
def readings():
    """`benchmark/tools/lm_control.py` at the rehearsal's size: the
    program (bf16 compute, as the cell runs it) and the control, each
    through the cell's own `check`."""
    from benchmark.tests import rehearse_lm
    from benchmark.tools import lm_control

    cell = _cell_config()
    return lm_control.readings(
        {**cell.config, **rehearse_lm.TINY_CONFIG},
        {**cell.traffic, **rehearse_lm.TINY_TRAFFIC}, seed=3_000_000_000)


def test_the_control_reads_far_above_the_program_at_a_tiny_size(readings):
    """Rounding's share of a gradient grows with the width (`PERF.md`,
    PR 32), so the limit that lies between the two readings at the timed
    sizes passes both here; what holds at every size is their order: the
    reference with its operands at 3 mantissa bits is off by twice the
    program's bf16 and more in its worst leaf, and by more in every leaf,
    while the loss sees neither."""
    program, control = readings["program"], readings["control"]
    assert program["ok"], program
    assert program["gap"] < ref.LOSS_TOLERANCE / 10
    assert control["gap"] < ref.LOSS_TOLERANCE / 10
    assert control["gradient_gap"] > 2 * program["gradient_gap"]
    for leaf, gap in control["gradient_gaps"].items():
        assert gap > program["gradient_gaps"][leaf], leaf


def _chip_readings():
    with open(os.path.join(os.path.dirname(ref.__file__), "tests",
                           "lm_control_readings.json")) as f:
        return [(r["seed"], which, r[which]) for r in json.load(f)["readings"]
                for which in ("program", "control")]


@pytest.mark.parametrize(
    "seed,which,got", _chip_readings(),
    ids=[f"{which}-{seed}" for seed, which, _ in _chip_readings()])
def test_the_limit_lies_between_the_chips_two_readings(seed, which, got):
    """`lm_control.py`'s readings on the chip at the timed sizes, kept as
    data: `check` passes the program's and refuses the control's, by the
    gradients and not by the loss (a limit moved past either reading
    fails here)."""
    verdict = ref.check(got["loss"], got["reference_loss"],
                        got["gradient_gaps"])
    assert verdict["ok"] is (which == "program"), verdict
    assert verdict["gap"] < ref.LOSS_TOLERANCE / 10
    room = verdict["gradient_gap"] / ref.GRADIENT_TOLERANCE
    assert room < 0.7 if which == "program" else room > 2.0


def test_gradient_gaps_are_a_leafs_over_all_its_layers():
    """`unstack` gives the program's tree a layer an entry; a gap is over
    a leaf's layers together, whichever form the two trees come in; and
    `check` fails on a leaf that is not finite."""
    model = get_model("qwen3-next", **TINY)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    want = jax.tree.map(
        lambda a: rng.normal(size=a.shape).astype(np.float32), shapes)
    flat = ref.unstack(want)
    assert len(flat["layers"]) == TINY["num_hidden_layers"]
    assert [sorted(layer) for layer in flat["layers"]] == 3 * [
        ["gdn", "moe", "norm1", "norm2"]] + [["attn", "moe", "norm1", "norm2"]]
    assert flat["layers"][2]["gdn"]["conv"].base is want["gdn"]["conv"]
    got = jax.tree.map(lambda a: 1.5 * a, want)
    got["gdn"]["out_proj"] = want["gdn"]["out_proj"].copy()
    got["gdn"]["out_proj"][0, 1] = 0.0          # one layer of three dropped
    for form in (got, ref.unstack(got)):
        gaps = ref.gradient_gaps(form, flat)
        assert sorted(gaps) == LEAVES
        assert gaps["['moe']['w_in']"] == pytest.approx(0.5, rel=1e-5)
        assert gaps["['gdn']['out_proj']"] == pytest.approx(
            3 ** -0.5, rel=0.05)
    assert not ref.check(9.0, 9.0, gaps)["ok"]      # 0.5 in every leaf
    fine = {k: 0.01 for k in gaps}
    assert ref.check(9.0, 9.0, fine)["ok"]
    assert not ref.check(9.0, 9.0 + 2 * ref.LOSS_TOLERANCE, fine)["ok"]
    over = 1.5 * ref.GRADIENT_TOLERANCE
    assert not ref.check(9.0, 9.0, {**fine, "['moe']['router']": over})["ok"]
    assert ref.check(9.0, 9.0, {**fine, ref.UNSTEADY[0]: over})["ok"]
    assert not ref.check(
        9.0, 9.0, {**fine, ref.UNSTEADY[0]: float("nan")})["ok"]


def test_configuration_keeps_every_published_width():
    cell = _cell_config()
    c = cell.config
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 32, 18992)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert c["num_experts_routed"] == 512 and c["num_experts_per_tok"] == 10
    widths = dict(
        hidden_size=2048, head_dim=256, num_attention_heads=16,
        num_key_value_heads=2, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_conv_kernel_dim=4,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        partial_rotary_factor=0.25, full_attention_interval=4)
    assert {k: c[k] for k in widths} == widths
    assert "16 chips" in c["deployment"] and "8 share" in c["deployment"]
    t = cell.traffic
    assert t["kind"] == "train_lm" and t["seq_len"] == 8192
    assert isinstance(t["weights_seed"], int) and t["mesh"] == {"data": 1}


def test_the_cut_holds_625_7_million_parameters():
    """ISSUE 32's table: 547.9 M in the four layers + 77.8 M embedding
    and head (norms and the small vectors on top: under 0.1 M)."""
    from benchmark.drivers import train_lm

    name, kw, _ = train_lm.model_of(_cell_config().config)
    shapes = jax.eval_shape(get_model(name, **kw).init, jax.random.PRNGKey(0))
    sizes = jax.tree.map(lambda a: a.size, shapes)
    total = sum(jax.tree.leaves(sizes))
    assert abs(total / 1e6 - 625.7) < 0.4, total
    assert sum(jax.tree.leaves(sizes["moe"])) == 4 * (
        2048 * 512 + 2048 + 3 * 2048 * 512 + 32 * 3 * 2048 * 512)
    assert sizes["tok_embed"] + sizes["head"] == 2 * 18992 * 2048


def test_flop_count_by_the_rows_routed():
    """Hand-worked at the published widths: a delta layer multiplies a
    token by 33.69 M weights, the attention layer by 27.26 M, an expert
    layer by 4.20 M + rows x 3.15 M, the head by 38.90 M."""
    from benchmark import lm_flops_bytes as fb

    c = _cell_config().config
    gdn = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    attn = 2048 * 8192 + 2048 * 1024 + 4096 * 2048
    fixed = 2048 * 512 + 2048 + 3 * 2048 * 512

    def want(rows):
        return (3 * gdn + attn + 4 * (fixed + rows * 3 * 2048 * 512)
                + 2048 * 18992)

    for rows in (0.0, 0.625, 10.0):
        assert fb.matmul_params_per_token(c, rows) == pytest.approx(want(rows))
    per_token = fb.train_flops_per_token(c, 8192, 0.625)
    assert per_token == pytest.approx(
        6 * want(0.625) + 12 * 4096 * 8192 + 3 * 3 * 6 * 32 * 128 * 128)
    flops, nbytes = fb.experts_step(c, 16384, 0.625)
    assert flops == pytest.approx(4 * 10240 * 18 * 2048 * 512)
    assert nbytes > 4 * 32 * 3 * 2048 * 512 * 2 * 3   # the weights, at least
    flops, nbytes = fb.delta_rule_step(c, 16384)
    assert flops == pytest.approx(3 * 3 * 16384 * 6 * 32 * 128 * 128)
    assert nbytes == 3 * 16384 * ((4096 + 2 * 4096) * 2 + 256
                                  + (2 * 4096 + 3 * 4096) * 2 + 512)
