"""Flash attention vs dense reference (CPU blockwise path + grads)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.ops import flash_attention
from determined_tpu.parallel.ring import reference_attention


def _rand_qkv(key, b, s, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, s, h, d), dtype),
        jax.random.normal(kk, (b, s, h, d), dtype),
        jax.random.normal(kv, (b, s, h, d), dtype),
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,block", [(64, 16), (128, 64), (96, 32)])
def test_flash_matches_dense(causal, s, block):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, s, 3, 16)
    got = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=block, block_k=block
        )
    )(q, k, v)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 2, 64, 2, 8)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=32, block_k=32) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_flash_bad_block():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 100, 1, 8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [16, 32])
@pytest.mark.parametrize("fused", [True, False])
def test_flash_pallas_bwd_interpret_matches(monkeypatch, causal, block, fused):
    """The Pallas backward kernels (the TPU path) against the blockwise
    reference backward, in interpret mode. Block 16 at s=64 exercises all
    three causal regimes (skip / masked diagonal / unmasked below)."""
    import importlib

    # `determined_tpu.ops.__init__` re-exports the flash_attention FUNCTION
    # under the same name, so `import ... as fa` would bind that instead
    # of the module.
    fa = importlib.import_module("determined_tpu.ops.flash_attention")
    from determined_tpu.ops.flash_attention import (
        _blockwise_bwd_ref,
        _blockwise_fwd_ref,
        _flash_bwd_pallas,
    )

    b, s, h, d = 1, 64, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), b, s, h, d)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    do = jax.random.normal(jax.random.PRNGKey(5), qf.shape)
    scale = 1.0 / d ** 0.5
    o, lse = _blockwise_fwd_ref(qf, kf, vf, scale=scale, causal=causal,
                                block_k=block)
    # Nonzero dlse: ring attention feeds a real lse cotangent through
    # whichever blocked path is active — it must be covered in both.
    dlse = jax.random.normal(jax.random.PRNGKey(6), lse.shape)
    want = _blockwise_bwd_ref(qf, kf, vf, o, lse, do, scale=scale,
                              causal=causal, block_k=block, dlse=dlse)
    # fused=True: the one-pass blocked kernel (dq in VMEM scratch);
    # fused=False: the two-pass dq + dkv split (the past-budget fallback).
    if not fused:
        monkeypatch.setattr(fa, "_DQ_VMEM_BUDGET", 0)
    got = _flash_bwd_pallas(qf, kf, vf, o, lse, do, scale=scale,
                            causal=causal, block_q=block, block_k=block,
                            interpret=True, dlse=dlse)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5,
            err_msg=name,
        )


@pytest.mark.parametrize("s_q,s_k,bq,bk,causal,window,segs,offset", [
    (64, 64, 16, 16, False, None, False, 0),
    (64, 64, 16, 16, True, None, False, 0),
    (64, 64, 16, 32, True, None, False, 0),     # block_q != block_k
    (64, 64, 16, 16, True, 23, False, 0),
    (64, 64, 16, 16, True, None, True, 0),
    (64, 64, 16, 16, False, None, True, 0),
    (64, 64, 16, 16, True, 17, True, 0),
    (32, 64, 16, 16, True, None, False, 32),    # kv_offset, s_q != s_k
    (32, 96, 16, 32, False, None, False, 0),    # s_q != s_k, not causal
    (48, 80, 16, 16, True, 40, False, 32),
], ids=["whole", "causal", "causal-rect", "window", "segments",
        "segments-whole", "window-segments", "kv-offset", "cross",
        "window-offset"])
def test_flash_fused_bwd_is_the_split_pair_to_the_bit(
        monkeypatch, s_q, s_k, bq, bk, causal, window, segs, offset):
    """The one-pass blocked backward, dq summed in VMEM scratch across the
    key blocks, gives the split dq / dkv pair's gradients bit for bit in
    float32: dq adds ds·k over the key blocks, dk and dv over the query
    blocks, in the order the pair does, from the same s, p, dp and ds. Its
    scratch starts as NaN here (the chip hands scratch out as the last
    kernel left it), so an accumulator not zeroed before its first sum
    fails; three heads share it, so one zeroed once a call fails too.
    Both are also the blockwise reference backward's, with an lse
    cotangent (ring attention's)."""
    import importlib

    from jax.experimental.pallas import tpu as pltpu

    fa = importlib.import_module("determined_tpu.ops.flash_attention")
    bh, d = 3, 16
    kq, kk, kv, kd, kl = jax.random.split(jax.random.PRNGKey(s_q + s_k), 5)
    q = jax.random.normal(kq, (bh, s_q, d))
    k = jax.random.normal(kk, (bh, s_k, d))
    v = jax.random.normal(kv, (bh, s_k, d))
    do = jax.random.normal(kd, q.shape)
    seg = None
    if segs:
        ids = _packed_segments(jax.random.PRNGKey(s_k), bh, s_k, 3)
        seg = (ids[:, offset:offset + s_q].astype(jnp.float32),
               ids.astype(jnp.float32))
    band = dict(scale=1.0 / d ** 0.5, causal=causal, window=window,
                kv_offset=offset, segs=seg)
    o, lse = fa._blockwise_fwd_ref(q, k, v, block_k=bk, **band)
    dlse = jax.random.normal(kl, lse.shape)

    def pallas(interpret):
        return fa._flash_bwd_pallas(q, k, v, o, lse, do, block_q=bq,
                                    block_k=bk, interpret=interpret,
                                    dlse=dlse, **band)

    fused = pallas(pltpu.InterpretParams(uninitialized_memory="nan"))
    monkeypatch.setattr(fa, "_DQ_VMEM_BUDGET", 0)
    split = pallas(True)
    want = fa._blockwise_bwd_ref(q, k, v, o, lse, do, block_k=bk, dlse=dlse,
                                 **band)
    for name, a, b_, w in zip(("dq", "dk", "dv"), fused, split, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                      err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=5e-5,
                                   rtol=5e-5, err_msg=name)


def test_flash_fused_bwd_takes_shapes_whose_dq_fits_the_budget(monkeypatch):
    """One blocked backward kernel while a head's fp32 dq fits
    `_DQ_VMEM_BUDGET` (the 8k cells' 8 MiB), the split pair past it: the
    choice is the shape's, and the split's two calls are the only way to
    two."""
    import importlib

    fa = importlib.import_module("determined_tpu.ops.flash_attention")
    assert 8192 * 256 * 4 <= fa._DQ_VMEM_BUDGET < 32768 * 256 * 4

    def calls(s, d):
        x = jax.ShapeDtypeStruct((2, s, d), jnp.bfloat16)
        vec = jax.ShapeDtypeStruct((2, s), jnp.float32)
        jaxpr = jax.make_jaxpr(functools.partial(
            fa._flash_bwd_pallas, scale=1.0, causal=True, block_q=512,
            block_k=512))(x, x, x, x, vec, x)
        return str(jaxpr).count("pallas_call")

    assert calls(8192, 256) == 1
    assert calls(16384, 256) == 1
    assert calls(32768, 256) == 2
    monkeypatch.setattr(fa, "_DQ_VMEM_BUDGET", 0)
    assert calls(8192, 256) == 2


def test_flash_pallas_interpret_matches():
    """Run the actual Pallas kernel in interpret mode against the reference."""
    from determined_tpu.ops.flash_attention import _flash_fwd_pallas

    b, s, h, d = 1, 64, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b, s, h, d)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    for causal in (False, True):
        o, lse = _flash_fwd_pallas(
            qf, kf, vf, scale=1.0 / d ** 0.5, causal=causal,
            block_q=32, block_k=32, interpret=True,
        )
        want = reference_attention(q, k, v, causal=causal)
        wf = want.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        np.testing.assert_allclose(np.asarray(o), np.asarray(wf), atol=2e-5, rtol=2e-5)


def _heads_to_rows(x, h):
    """[BH, S, D] → [B, H*D, S], the monolithic kernels' operand."""
    bh, s, d = x.shape
    return x.transpose(0, 2, 1).reshape(bh // h, h * d, s)


def _rows_to_heads(xt, h):
    b, hd, s = xt.shape
    return xt.reshape(b * h, hd // h, s).transpose(0, 2, 1)


def _mono_case(s, causal, h, d, fused, b=1):
    """The monolithic kernels (interpret mode) on [B, H*D, S] operands, or
    on the one fused [B, 3, H*D, S] projection, against the blockwise
    reference on [BH, S, D]: o, lse, and dq / dk / dv with an lse
    cotangent (the path ring attention feeds)."""
    from determined_tpu.ops.flash_attention import (
        _blockwise_bwd_ref,
        _blockwise_fwd_ref,
        _mono_bwd_pallas,
        _mono_fwd_pallas,
        _mono_ok,
        _mono_tiles,
    )

    assert _mono_ok(s, s, s, s) and _mono_tiles(d, jnp.float32)
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b, s, h, d)
    qf, kf, vf = (x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
                  for x in (q, k, v))
    scale = 1.0 / d ** 0.5
    if fused:  # as GPT's `bsd,dthk->bsthk` leaves it, sequence last
        ops = (jnp.stack([q, k, v], axis=2).transpose(0, 2, 3, 4, 1)
               .reshape(b, 3, h * d, s),)
    else:
        ops = tuple(_heads_to_rows(x, h) for x in (qf, kf, vf))

    ot, lse = _mono_fwd_pallas(ops, h=h, scale=scale, causal=causal,
                               interpret=True)
    o_want, lse_want = _blockwise_fwd_ref(
        qf, kf, vf, scale=scale, causal=causal, block_k=16
    )
    np.testing.assert_allclose(np.asarray(_rows_to_heads(ot, h)),
                               np.asarray(o_want), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_want),
                               atol=2e-5, rtol=2e-5)

    do = jax.random.normal(jax.random.PRNGKey(8), qf.shape)
    dlse = jax.random.normal(jax.random.PRNGKey(9), lse.shape)
    want = _blockwise_bwd_ref(qf, kf, vf, o_want, lse_want, do, scale=scale,
                              causal=causal, block_k=16, dlse=dlse)
    got = _mono_bwd_pallas(ops, _heads_to_rows(o_want, h), lse_want,
                           _heads_to_rows(do, h), dlse, h=h, scale=scale,
                           causal=causal, interpret=True)
    if fused:
        (got,) = got
        assert got.shape == ops[0].shape
        got = [got[:, part] for part in range(3)]
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(_rows_to_heads(a, h)), np.asarray(b_), atol=5e-5,
            rtol=5e-5, err_msg=name,
        )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [64, 128, 256, 384, 512, 640, 1024])
def test_flash_pallas_monolithic_interpret_matches(s, causal):
    """The monolithic single-block kernels (block == seq, the GPT-2-class
    fast path: plain softmax forward + fused single-pass backward, causal
    row chunks inside) against the blockwise reference — including the lse
    output and the dlse cotangent path that ring attention feeds. Causal
    chunks (512 rows forward, 256 backward): 64 to 256 are one chunk in
    both passes, 384 has a ragged last chunk backward (256 + 128), 640
    one forward (512 + 128), 1024 is the benchmark's 2 and 4."""
    _mono_case(s, causal, h=2, d=16, fused=False)


@pytest.mark.parametrize("causal,fused", [(True, True), (False, False)],
                         ids=["causal-fused", "whole-split"])
@pytest.mark.parametrize("s,h,d", [
    (256, 12, 64), (1024, 12, 64),    # GPT-2 small's heads
    (256, 4, 128), (1024, 4, 128),    # a head that fills the lanes
    (256, 2, 64), (1024, 2, 64),
    (256, 25, 64),                    # GPT-2 XL: 1600 rows, no 128 divides
])
def test_flash_mono_reads_heads_where_the_projection_leaves_them(
        s, h, d, causal, fused):
    """A head is d rows of [B, H*D, S] (or of each part of the fused
    [B, 3, H*D, S]): any head count tiles, 25 x 64 as well as 12 x 64."""
    _mono_case(s, causal, h, d, fused)


@pytest.mark.parametrize("fused", [False, True], ids=["split", "fused"])
def test_flash_mono_public_path_differentiates_o_and_lse(monkeypatch, fused):
    """`flash_attention_lse` / `flash_attention_qkv` end to end through the
    monolithic kernels (interpret mode), gradients of a loss that reads o
    AND lse (ring attention's contract) against the dense reference; a
    head width the kernels cannot tile (12: not whole sublane tiles) takes
    the folded [BH, S, D] kernels and agrees too."""
    import importlib

    fa = importlib.import_module("determined_tpu.ops.flash_attention")
    calls = []

    def interpreted(fn):
        def call(*args, interpret=False, **kw):
            calls.append(fn.__name__)
            return fn(*args, interpret=True, **kw)
        return call

    for name in ("_mono_fwd_pallas", "_mono_bwd_pallas", "_flash_fwd_pallas",
                 "_flash_bwd_pallas"):
        monkeypatch.setattr(fa, name, interpreted(getattr(fa, name)))
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    b, s, h = 2, 128, 3

    def ref_lse(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        return jax.nn.logsumexp(sc, axis=-1).transpose(0, 2, 1)

    for d, kernels in ((16, "_mono"), (12, "_flash")):
        del calls[:]
        q, k, v = _rand_qkv(jax.random.PRNGKey(d), b, s, h, d)

        def loss(q, k, v):
            if fused:
                o = fa.flash_attention_qkv(
                    jnp.stack([q, k, v], axis=2), block_q=s, block_k=s)
                return jnp.sum(o ** 2)
            o, lse = fa.flash_attention_lse(q, k, v, block_q=s, block_k=s)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

        def want(q, k, v):
            o = reference_attention(q, k, v, causal=True)
            extra = 0.0 if fused else jnp.sum(jnp.sin(ref_lse(q, k, v)))
            return jnp.sum(o ** 2) + extra

        got = jax.grad(loss, (0, 1, 2))(q, k, v)
        assert calls and all(c.startswith(kernels) for c in calls), calls
        for name, a, b_ in zip("qkv", got, jax.grad(want, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name} at d={d}")


# ---------------------------------------------------------------------------
# Band (window/kv_offset) + segment masking parity
# ---------------------------------------------------------------------------
def _packed_segments(key, b, s, n_docs):
    """[B, S] int32 ids: contiguous runs 1..n_docs with random boundaries
    (deterministic per key), mimicking pack_sequences output."""
    lens = np.asarray(
        jax.random.dirichlet(key, jnp.ones(n_docs) * 2.0, (b,)) * s
    ).astype(int)
    ids = np.zeros((b, s), np.int32)
    for r in range(b):
        pos = 0
        for d in range(n_docs):
            n = max(1, int(lens[r, d])) if d < n_docs - 1 else s - pos
            ids[r, pos: pos + max(0, n)] = d + 1
            pos = min(s, pos + n)
            if pos >= s:
                break
        ids[r, pos:] = n_docs  # tail joins the last doc
    return jnp.asarray(ids)


def _masked_parity_case(s, block, causal, window, with_segs, *, b=2, h=2,
                        d=16, check_grads=True):
    """One parity case: public flash_attention (CPU blockwise path) AND the
    Pallas kernels in interpret mode vs the dense reference — forward,
    lse, and input grads."""
    from determined_tpu.ops.flash_attention import (
        _flash_bwd_pallas,
        _flash_fwd_pallas,
        _blockwise_fwd_ref,
        fit_block,
        flash_attention_lse,
    )

    q, k, v = _rand_qkv(jax.random.PRNGKey(s * 7 + block), b, s, h, d)
    seg = (
        _packed_segments(jax.random.PRNGKey(s + 3), b, s, 3)
        if with_segs else None
    )
    # Ragged seq % block != 0 degrades via fit_block (the dispatcher's
    # contract); the kernel itself requires block | seq.
    bf = fit_block(s, block)

    def flash_fn(q, k, v):
        o, lse = flash_attention_lse(
            q, k, v, causal=causal, window=window, segment_ids=seg,
            block_q=bf, block_k=bf,
        )
        return o, lse

    got, lse = jax.jit(flash_fn)(q, k, v)
    want = reference_attention(
        q, k, v, causal=causal, window=window, segment_ids=seg
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )

    # The Pallas kernels (interpret mode) against the same oracle: fold to
    # [BH, S, D] and drive fwd directly; bwd vs the blockwise reference.
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    segs = None
    if seg is not None:
        segf = jnp.broadcast_to(
            seg[:, None, :].astype(jnp.float32), (b, h, s)
        ).reshape(b * h, s)
        segs = (segf, segf)
    scale = 1.0 / d ** 0.5
    o_pl, lse_pl = _flash_fwd_pallas(
        qf, kf, vf, scale=scale, causal=causal, window=window, segs=segs,
        block_q=bf, block_k=bf, interpret=True,
    )
    wf = want.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    np.testing.assert_allclose(
        np.asarray(o_pl), np.asarray(wf), atol=2e-5, rtol=2e-5
    )
    lse_w = lse.transpose(0, 2, 1).reshape(b * h, s)
    np.testing.assert_allclose(
        np.asarray(lse_pl), np.asarray(lse_w), atol=2e-5, rtol=2e-5
    )

    if not check_grads:
        return

    def loss_flash(q, k, v):
        o, lse = flash_fn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        o = reference_attention(
            q, k, v, causal=causal, window=window, segment_ids=seg
        )
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g_flash = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name}",
        )

    # Pallas backward kernels in interpret mode vs the blockwise backward.
    o_ref, lse_ref2 = _blockwise_fwd_ref(
        qf, kf, vf, scale=scale, causal=causal, window=window, segs=segs,
        block_k=bf,
    )
    do = jax.random.normal(jax.random.PRNGKey(9), qf.shape)
    dlse = jax.random.normal(jax.random.PRNGKey(10), lse_ref2.shape)
    from determined_tpu.ops.flash_attention import _blockwise_bwd_ref

    want_g = _blockwise_bwd_ref(
        qf, kf, vf, o_ref, lse_ref2, do, scale=scale, causal=causal,
        window=window, segs=segs, block_k=bf, dlse=dlse,
    )
    got_g = _flash_bwd_pallas(
        qf, kf, vf, o_ref, lse_ref2, do, scale=scale, causal=causal,
        window=window, segs=segs, block_q=bf, block_k=bf, interpret=True,
        dlse=dlse,
    )
    for name, a, b_ in zip(("dq", "dk", "dv"), got_g, want_g):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5,
            err_msg=name,
        )


@pytest.mark.parametrize("window", [1, 17, 64])
def test_flash_window_matches_dense(window):
    """Tier-1: sliding-window causal — CPU path, Pallas interpret, grads."""
    _masked_parity_case(64, 16, causal=True, window=window, with_segs=False)


def test_flash_segments_match_dense():
    """Tier-1: packed-sequence segment masking, causal."""
    _masked_parity_case(64, 16, causal=True, window=None, with_segs=True)


def test_flash_window_plus_segments_match_dense():
    """Tier-1: window AND segments composed."""
    _masked_parity_case(64, 16, causal=True, window=23, with_segs=True)


def test_flash_segments_noncausal_matches_dense():
    _masked_parity_case(64, 16, causal=False, window=None, with_segs=True)


def test_flash_ragged_fit_block_window():
    """Tier-1: seq % wanted-block != 0 — fit_block degrades the tile and
    the masked kernels stay correct."""
    _masked_parity_case(96, 64, causal=True, window=31, with_segs=True)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("window", [None, 1, 9, 33, 128])
@pytest.mark.parametrize("with_segs", [False, True])
@pytest.mark.parametrize("s,block", [(64, 16), (128, 64), (96, 32), (80, 32)])
def test_flash_masked_parity_sweep(causal, window, with_segs, s, block):
    """Full parity sweep (slow): causal × window × segments × ragged."""
    if window is not None and not causal:
        pytest.skip("window requires causal")
    _masked_parity_case(s, block, causal=causal, window=window,
                        with_segs=with_segs)


def test_flash_kv_offset_decode_layout():
    """causal + kv_offset: a short q block bottom-aligned against a longer
    k (the decode/kv-cache geometry, and ring attention's hop geometry)."""
    from determined_tpu.ops.flash_attention import (
        _flash_fwd_pallas,
        flash_attention,
    )

    b, s_k, h, d = 2, 64, 2, 16
    s_q, off = 16, 48
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b, s_k, h, d)
    q1 = q[:, :s_q]
    got = flash_attention(
        q1, k, v, causal=True, kv_offset=off, block_q=16, block_k=16
    )
    scale = 1.0 / d ** 0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q1, k) * scale
    mask = (jnp.arange(s_q)[:, None] + off) >= jnp.arange(s_k)[None, :]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    want = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )
    # Pallas interpret path too (different kernel from the CPU blockwise).
    qf = q1.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s_k, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s_k, d)
    o_pl, _ = _flash_fwd_pallas(
        qf, kf, vf, scale=scale, causal=True, kv_offset=off,
        block_q=16, block_k=16, interpret=True,
    )
    wf = want.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
    np.testing.assert_allclose(
        np.asarray(o_pl), np.asarray(wf), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("s_k,off", [(128, 127), (256, 255), (192, 100)])
def test_flash_single_token_decode_parity(s_k, off):
    """q_len=1 (a sub-block query) with a large kv_offset — the exact
    degenerate geometry the serving engine's decode step leans on (one
    new token against a long paged cache, optionally with segment ids
    trimming a dead tail). Checked against reference_attention on both
    the CPU blockwise path and the Pallas kernel in interpret mode."""
    from determined_tpu.ops.flash_attention import _flash_fwd_pallas

    b, h, d = 2, 3, 16
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q1 = jax.random.normal(kq, (b, 1, h, d))
    k = jax.random.normal(kk, (b, s_k, h, d))
    v = jax.random.normal(kv, (b, s_k, h, d))

    # the row sits at absolute position `off`: it attends keys [0, off]
    live = off + 1
    got = flash_attention(
        q1, k, v, causal=True, kv_offset=off, block_q=1, block_k=32
    )
    want = reference_attention(q1[:, :1], k[:, :live], v[:, :live],
                               causal=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )

    # segment ids trimming a dead tail shorter than the causal reach —
    # the paged-decode mask shape (cache rows past `length` are garbage)
    length = live - 16
    qseg = jnp.ones((b, 1), jnp.int32)
    kseg = (jnp.arange(s_k)[None, :] < length).astype(jnp.int32)
    kseg = jnp.broadcast_to(kseg, (b, s_k))
    got_seg = flash_attention(
        q1, k, v, causal=True, kv_offset=off, block_q=1, block_k=32,
        segment_ids=qseg, kv_segment_ids=kseg,
    )
    want_seg = reference_attention(
        q1[:, :1], k[:, :length], v[:, :length], causal=False
    )
    np.testing.assert_allclose(
        np.asarray(got_seg), np.asarray(want_seg), atol=2e-5, rtol=2e-5
    )

    # the Pallas kernel itself (interpret mode; the blocked grid, since
    # kv_offset != 0 never takes the mono path)
    scale = 1.0 / d ** 0.5
    qf = q1.transpose(0, 2, 1, 3).reshape(b * h, 1, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s_k, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s_k, d)
    o_pl, _ = _flash_fwd_pallas(
        qf, kf, vf, scale=scale, causal=True, kv_offset=off,
        block_q=1, block_k=32, interpret=True,
    )
    wf = want.transpose(0, 2, 1, 3).reshape(b * h, 1, d)
    np.testing.assert_allclose(
        np.asarray(o_pl), np.asarray(wf), atol=2e-5, rtol=2e-5
    )


def test_flash_window_validation():
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 1, 64, 1, 8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, window=0)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, kv_offset=-1)


def test_block_skip_stats_counts():
    """The bench-reporting mirror matches a brute-force element mask: a
    block is live iff it contains at least one unmasked element."""
    from determined_tpu.ops.flash_attention import block_skip_stats

    for s, bq, bk, window, off in [
        (64, 16, 16, None, 0),
        (64, 16, 32, 20, 0),
        (128, 32, 32, 48, 0),
        (64, 16, 16, None, 64),
        (96, 32, 32, 7, 0),
    ]:
        rows = np.arange(s)[:, None] + off
        cols = np.arange(s)[None, :]
        m = rows >= cols
        if window is not None:
            m &= rows - cols < window
        nq, nk = s // bq, s // bk
        brute = sum(
            bool(m[i * bq: (i + 1) * bq, j * bk: (j + 1) * bk].any())
            for i in range(nq) for j in range(nk)
        )
        live, total = block_skip_stats(
            s, s, bq, bk, causal=True, window=window, kv_offset=off
        )
        assert total == nq * nk
        assert live == brute, (s, bq, bk, window, off, live, brute)


@pytest.mark.parametrize("s,causal,want", [
    (1024, True, (3, 4)),    # two chunks of 512 rows: 1 + 2 of 2 x 2
    (1408, True, (6, 9)),    # near the largest square the mono path takes
    (640, True, (3, 4)),     # ragged last chunk: 512 + 128 rows
    (512, True, (1, 1)),     # s <= chunk: one chunk
    (64, True, (1, 1)),
    (1024, False, (1, 1)),   # not causal: one chunk, every key
])
def test_block_skip_stats_counts_mono_chunks(s, causal, want):
    """On the mono path (block == seq) the blocks are the forward kernel's
    causal row chunks by as many keys, and every chunk's live keys are what
    the kernel slices for it."""
    from determined_tpu.ops.flash_attention import (
        _MONO_CHUNK_FWD,
        _mono_chunks,
        _mono_ok,
        block_skip_stats,
    )

    assert _MONO_CHUNK_FWD == 512 and _mono_ok(s, s, s, s)
    assert block_skip_stats(s, s, s, s, causal=causal) == want
    for r0, r1, k1 in _mono_chunks(s, s, causal, _MONO_CHUNK_FWD):
        assert k1 == (r1 if causal else s)  # no live key is left out
