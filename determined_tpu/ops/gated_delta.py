"""The gated delta rule, computed in chunks.

The recurrence, for one value head with state S in R^{Dk x Dv}, S_0 = 0:

    S   <- exp(g_t) S
    d_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

(`benchmark/reference_qwen3_next.py::delta_rule` is exactly that, a
`lax.scan` over tokens, and what the tests hold this file to.) A train
step cannot walk 8192 tokens one at a time, so `gated_delta_chunked` cuts
the sequence into
chunks of C tokens and uses that, inside a chunk that starts from state
S_0, with G_t the running sum of g within the chunk,

    (I + A) D = beta V - (beta e^G K) S_0,
        A[t, s] = beta_t e^{G_t - G_s} (k_t . k_s) for s < t, else 0
    O   = (e^G Q) S_0 + (M * Q K^T) D,   M[t, s] = e^{G_t - G_s}, s <= t
    S_C = e^{G_C} S_0 + (e^{G_C - G} K)^T D

so with T = (I + A)^{-1}, W = T (beta e^G K) and U = T (beta V):
D = U - W S_0. Everything that does not touch S_0 (T, W, U, the masked
Q K^T) is computed for all chunks at once, as batched matmuls, and is
jax's to differentiate; the triangular solve is the explicit inverse of a
unit lower-triangular C x C matrix (`_unit_lower_inverse`: squarings of
32-wide blocks, merged by halves, float32).

What is left is the recurrence over chunks, `_recurrence_step`: from S_0
and a chunk's W, U, M * Q K^T, Q, K and running sums G to S_C and O (it
forms e^G Q and e^{G_C - G} K itself: a row of Q times a number). On a
TPU it runs as one Pallas kernel forward and one backward a call
(`_recurrence_kernels`, a `jax.custom_vjp`): grid (blocks of value heads,
chunks), the chunks in order, the float32 state (backward: its
cotangent, the chunks last to first) in VMEM scratch from one chunk to
the next, every operand read through its `BlockSpec` where the batched
half left it. The forward that a backward follows also writes the state
ENTERING each chunk; the backward kernel's body is `jax.vjp` of
`_recurrence_step` on the blocks it loaded, so no cotangent here is a
derivation. Elsewhere, and as the tests' oracle, the same step runs under
a `lax.scan` with jax's own backward. As a scan on the chip it was 64
trips of 6-17 small operations, three times a layer, each trip's state
through HBM: bound by the launches, not the work, 14 of a step's 349 ms
in `qwen3next-train-8k-ep16share`, and as much again in layout copies
in front of it (PERF.md, PR 37; C = 128 still stands: the inverse's C^3
takes over above it).

Callers put the call under `jax.checkpoint` (models/qwen3_next.py), so
the states a chunk (134 MB a layer there) live only while one layer's
backward runs; a state a token is never kept.

Heads: q and k have Hk heads, v Hv = Hk * r; key head h serves value
heads h*r .. h*r + r - 1. K K^T and Q K^T are computed once a key head.
Precision: g, the running sums, A, T and the state are float32; the
matmuls take their operands in the inputs' dtype (bf16 in training)
and accumulate in float32.
"""
from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
from jax import lax

# The module, not the function `determined_tpu.ops` re-exports under its
# name: for its deferred `pl`, and for its answer to whether the backend is
# a TPU, so that whoever compiles a step for a described chip answers that
# once for both.
_flash = importlib.import_module("determined_tpu.ops.flash_attention")

#: float32 products of the triangular inverse: three bf16 passes on the MXU
#: (about 2^-16 relative), half the six of HIGHEST; full float32 off the chip.
HIGH = lax.Precision.HIGH


#: Diagonal blocks up to this size are inverted by squarings; larger ones
#: from their two halves.
_INVERSE_BASE = 32


@jax.custom_vjp
def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + a)^{-1} for strictly lower-triangular a [..., C, C], float32.

    A block up to `_INVERSE_BASE` wide: a is nilpotent (a^C = 0), so with
    b = -a the inverse is the finite series (I + b)(I + b^2)(I + b^4)...,
    log2 C squarings. A larger one from its halves,

        [[X11, 0], [a21, X22]]^-1 = [[T11, 0], [-T22 a21 T11, T22]],

    two products of half the width: the squarings alone re-read and
    re-write the whole [..., C, C] array twice a squaring, which at C =
    128 was half the chunked rule's time on the chip (PERF.md, PR 32)."""
    return _unit_lower_inverse_fwd(a)[0]


def _inverse_by_squarings(a):
    c = a.shape[-1]
    t, p, n = jnp.eye(c, dtype=a.dtype) - a, a, 1   # p = a^n = (-a)^n, n even
    while 2 * n < c:
        p = jnp.matmul(p, p, precision=HIGH)
        t = t + jnp.matmul(t, p, precision=HIGH)
        n *= 2
    return t


def _inverse_by_halves(a):
    c = a.shape[-1]
    if c <= _INVERSE_BASE or c % 2:
        return _inverse_by_squarings(a)
    h = c // 2
    t11 = _inverse_by_halves(a[..., :h, :h])
    t22 = _inverse_by_halves(a[..., h:, h:])
    t21 = -jnp.matmul(jnp.matmul(t22, a[..., h:, :h], precision=HIGH), t11,
                      precision=HIGH)
    return jnp.concatenate([
        jnp.concatenate([t11, jnp.zeros_like(t21)], axis=-1),
        jnp.concatenate([t21, t22], axis=-1)], axis=-2)


def _unit_lower_inverse_fwd(a):
    t = _inverse_by_halves(a)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    # d(X^-1) = -X^-1 dX X^-1, so the cotangent of X is -T^T dT T^T; only
    # the strictly lower part of X = I + a is a's.
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(jnp.matmul(tt, dt, precision=HIGH), tt, precision=HIGH)
    return (jnp.tril(da, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _dot(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


# -- the recurrence over chunks ----------------------------------------------
def _as_column(row):
    """[..., 1, C] -> [..., C, 1] by a mask and a sum along the lanes: a
    transpose the chip's compiler takes at any C, and jax transposes."""
    c = row.shape[-1]
    eye = (lax.broadcasted_iota(jnp.int32, (c, c), 0)
           == lax.broadcasted_iota(jnp.int32, (c, c), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)


def _recurrence_step(state, w, u, p, q, k, gsum):
    """One chunk, for a block of H value heads: state [H, Dk, Dv] float32
    (entering the chunk), w = W [H, C, Dk], u = U [H, C, Dv], p = M * Q K^T
    [H, C, C]; q, k [H / r, C, Dk], a key head for the r value heads it
    serves; gsum = G [H, 1, C] float32 -> (the state leaving the chunk,
    o [H, C, Dv]). Written once: the scan, the forward kernel and, through
    `jax.vjp`, the backward kernel all run this."""
    cd = u.dtype
    c, r = gsum.shape[-1], w.shape[0] // q.shape[0]
    g = _as_column(gsum)                                   # [H, C, 1]
    g_end = g[:, -1:]                                      # G_C [H, 1, 1]
    # widened before it is repeated: the r cotangents add up in float32
    qg, kd = (
        (scale * jnp.repeat(x.astype(jnp.float32), r, axis=0)).astype(cd)
        for scale, x in ((jnp.exp(g), q), (jnp.exp(g_end - g), k)))
    # e^{G_C} for the state along the lanes, [H, 1, Dv]: against g_end's
    # [H, 1, 1] the backward would sum over the state's two dimensions
    # at once, which the chip's compiler does not take
    last = lax.broadcasted_iota(jnp.int32, (c, u.shape[-1]), 0) == c - 1
    decay = jnp.exp(jnp.sum(jnp.where(last, g, 0.0), axis=1, keepdims=True))
    s = state.astype(cd)
    d = (u.astype(jnp.float32) - _dot("htk,hkv->htv", w, s)).astype(cd)
    o = _dot("htk,hkv->htv", qg, s) + _dot("hts,hsv->htv", p, d)
    state = decay * state + _dot("htk,htv->hkv", kd, d)
    return state, o.astype(cd)


def _recurrence_scan(w, u, p, q, k, gsum):
    """o [H, N, C, Dv] of operands [H or H / r, N, ...]: `lax.scan` over
    the chunks, and jax's own backward of it."""
    chunks_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    state0 = jnp.zeros((w.shape[0], w.shape[-1], u.shape[-1]), jnp.float32)
    _, o = lax.scan(
        lambda state, xs: _recurrence_step(state, *xs), state0,
        tuple(chunks_first(x) for x in (w, u, p, q, k, gsum)))
    return jnp.moveaxis(o, 0, 1)


#: Value heads a program of the kernels holds: independent chains for the
#: MXUs to interleave, a 64 KB float32 state each at 128 x 128.
_HEADS_A_PROGRAM = 8


def _over_chunks(name, body, ins, out_shape, *, backwards, interpret):
    """`body(in refs, out refs, scratch)` as a Pallas kernel over the grid
    (blocks of heads, chunks): `ins` (w, u, p, q, k, ... each [H or H / r,
    N, ...]) and the outputs are read and written a block of heads and a
    chunk a step, where they lie; the chunks run in order (`backwards`:
    last to first), one block's after another's, with a float32
    [heads a block, Dk, Dv] scratch in VMEM carried from step to step and
    zeroed at a block's first: the chip hands scratch out as the last
    kernel left it."""
    from jax.experimental.pallas import tpu as pltpu

    w, u, _p, q = ins[:4]
    h, n = w.shape[:2]
    # a program holds whole key heads' value heads: `_HEADS_A_PROGRAM`
    # of them, or the r of one key head
    blocks = q.shape[0] // math.gcd(
        q.shape[0], max(1, _HEADS_A_PROGRAM * q.shape[0] // h))

    def specs(arrays):
        return [_flash.pl.BlockSpec(
            (x.shape[0] // blocks, None) + x.shape[2:],
            lambda i, j: (i, n - 1 - j if backwards else j, 0, 0))
            for x in arrays]

    def kernel(*refs):
        scratch = refs[-1]

        @_flash.pl.when(_flash.pl.program_id(1) == 0)
        def _():
            scratch[...] = jnp.zeros_like(scratch)

        body(refs[:len(ins)], refs[len(ins):-1], scratch)

    return _flash.pl.pallas_call(
        kernel,
        grid=(blocks, n),
        in_specs=specs(ins),
        out_specs=specs(out_shape),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(
            (h // blocks, w.shape[-1], u.shape[-1]), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )(*ins)


def _recurrence_call(operands, *, stash, interpret):
    """The forward kernel, the state in the scratch. `stash` also writes
    the state ENTERING each chunk, float32 [H, N, Dk, Dv]: what the
    backward kernel rebuilds a chunk from."""
    w, u = operands[:2]
    h, n, c, dk = w.shape
    dv = u.shape[-1]

    def body(ins, outs, state):
        entering = state[...]
        state[...], outs[0][...] = _recurrence_step(
            entering, *(r[...] for r in ins))
        if stash:
            outs[1][...] = entering

    out_shape = [jax.ShapeDtypeStruct((h, n, c, dv), u.dtype)]
    if stash:
        out_shape.append(jax.ShapeDtypeStruct((h, n, dk, dv), jnp.float32))
    return _over_chunks("gdn_recurrence_fwd", body, operands, out_shape,
                        backwards=False, interpret=interpret)


def _recurrence_bwd_call(operands, states, do, *, interpret):
    """The backward kernel, the state's cotangent in the scratch (zero
    behind the last chunk, where it starts). A chunk's cotangents are
    `jax.vjp` of `_recurrence_step` itself, traced into the kernel's body
    on the blocks it loaded: the state entering the chunk, the chunk's
    operands, and (dS', do)."""
    def body(ins, outs, dstate):
        *chunk, state, do_n = (r[...] for r in ins)
        _, vjp = jax.vjp(_recurrence_step, state, *chunk)
        dstate[...], *cotangents = vjp((dstate[...], do_n))
        for ref, ct in zip(outs, cotangents):
            ref[...] = ct

    return _over_chunks(
        "gdn_recurrence_bwd", body, operands + (states, do),
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in operands],
        backwards=True, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _recurrence_kernels(w, u, p, q, k, gsum, interpret):
    """`_recurrence_scan` as a Pallas kernel pair."""
    return _recurrence_call(
        (w, u, p, q, k, gsum), stash=False, interpret=interpret)[0]


def _recurrence_kernels_fwd(w, u, p, q, k, gsum, interpret):
    operands = (w, u, p, q, k, gsum)
    o, states = _recurrence_call(operands, stash=True, interpret=interpret)
    return o, (operands, states)


def _recurrence_kernels_bwd(interpret, res, do):
    operands, states = res
    return tuple(_recurrence_bwd_call(
        operands, states, do, interpret=interpret))


_recurrence_kernels.defvjp(_recurrence_kernels_fwd, _recurrence_kernels_bwd)


def gated_delta_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    *, chunk: int = 128, interpret: bool = False,
) -> jax.Array:
    """q, k [B, S, Hk, Dk] (normalised and scaled by the caller), v
    [B, S, Hv, Dv], g (log decay, <= 0) and beta [B, S, Hv] -> o
    [B, S, Hv, Dv] in v's dtype. Any S: the tail is padded with tokens
    that leave the state alone (k = v = beta = g = 0). The recurrence
    over chunks runs as the Pallas kernels on a TPU at widths the lanes
    hold whole and as the `lax.scan` elsewhere; `interpret`
    (`pallas_call`'s) has the tests run the kernels anywhere."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    assert hk * r == hv, (hk, hv)
    cd = v.dtype
    c = chunk
    n = -(-s // c)
    pad = n * c - s
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))

    with jax.named_scope("gdn_scan"):
        # [B, Hk, (r,) N, C, D]: heads outside, chunks next, a chunk's
        # tokens and the head width last (the matmuls' two dimensions).
        qc = q.reshape(b, n, c, hk, dk).transpose(0, 3, 1, 2, 4)
        kc = k.reshape(b, n, c, hk, dk).transpose(0, 3, 1, 2, 4)
        vc = v.reshape(b, n, c, hk, r, dv).transpose(0, 3, 4, 1, 2, 5)
        gc = g.astype(jnp.float32).reshape(b, n, c, hk, r).transpose(
            0, 3, 4, 1, 2)
        bc = beta.astype(jnp.float32).reshape(b, n, c, hk, r).transpose(
            0, 3, 4, 1, 2)

        gsum = jnp.cumsum(gc, axis=-1)                     # G_t
        gdiff = gsum[..., :, None] - gsum[..., None, :]    # G_t - G_s
        tri = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.where(tri, jnp.exp(jnp.where(tri, gdiff, 0.0)), 0.0)
        kk = _dot("bhntd,bhnsd->bhnts", kc, kc)[:, :, None]
        qk = _dot("bhntd,bhnsd->bhnts", qc, kc)[:, :, None]
        a = jnp.tril(bc[..., :, None] * decay * kk, -1)
        t = _unit_lower_inverse(a)                         # [B,Hk,r,N,C,C]
        kf = kc.astype(jnp.float32)[:, :, None]
        w = jnp.einsum("bhrnts,bhrnsd->bhrntd", t,
                       (bc * jnp.exp(gsum))[..., None] * kf,
                       precision=HIGH).astype(cd)
        u = jnp.einsum("bhrnts,bhrnsd->bhrntd", t,
                       bc[..., None] * vc.astype(jnp.float32),
                       precision=HIGH).astype(cd)
        p = (decay * qk).astype(cd)                        # M * Q K^T

        # [B * Hv (or B * Hk), N, ...]: a head a row, where the batched
        # half leaves each operand: the reshapes move nothing. `g` and
        # `beta` are one a value head, so `w`, `u`, `p` and the sums are;
        # q and k stay one a key head.
        heads = lambda x, d: x.reshape((-1, n) + x.shape[d:])  # noqa: E731
        operands = (heads(w, 4), heads(u, 4), heads(p, 4), heads(qc, 3),
                    heads(kc, 3), heads(gsum[..., None, :], 4))
        if interpret or (_flash._use_pallas() and c % 128 == 0
                         and dk % 128 == 0 and dv % 128 == 0):
            o = _recurrence_kernels(*operands, interpret)
        else:
            o = _recurrence_scan(*operands)
        # [B * Hv, N, C, Dv] -> [B, S, Hv, Dv]
        o = o.reshape(b, hv, n * c, dv).transpose(0, 2, 1, 3)
    return o[:, :s]
