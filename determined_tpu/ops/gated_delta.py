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
Q K^T) is computed for all chunks at once, as batched matmuls; the
triangular solve is the explicit inverse of a unit lower-triangular
C x C matrix (`_unit_lower_inverse`: squarings of 32-wide blocks, merged
by halves, float32); and one
`lax.scan` over chunks carries the state, three small matmuls a chunk
(C = 128: the scan is bound by its steps' latency, not their work, so
fewer and larger steps win until the inverse's C^3 takes over).

The backward is jax's own of that program. What it keeps is a state a
CHUNK (the scan's carry) and never a state a token; callers put the call
under `jax.checkpoint` (models/qwen3_next.py), so that even those live
only while one layer's backward runs.

Heads: q and k have Hk heads, v Hv = Hk * r; key head h serves value
heads h*r .. h*r + r - 1. K K^T and Q K^T are computed once a key head.
Precision: g, the running sums, A, T and the state are float32; the
matmuls take their operands in the inputs' dtype (bf16 in training)
and accumulate in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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


def gated_delta_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    *, chunk: int = 128,
) -> jax.Array:
    """q, k [B, S, Hk, Dk] (normalised and scaled by the caller), v
    [B, S, Hv, Dv], g (log decay, <= 0) and beta [B, S, Hv] -> o
    [B, S, Hv, Dv] in v's dtype. Any S: the tail is padded with tokens
    that leave the state alone (k = v = beta = g = 0)."""
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
        qg = (jnp.exp(gsum)[..., None]
              * qc.astype(jnp.float32)[:, :, None]).astype(cd)
        g_end = gsum[..., -1]                              # G_C [B,Hk,r,N]
        kd = (jnp.exp(g_end[..., None] - gsum)[..., None] * kf).astype(cd)

        wq = jnp.concatenate([w, qg], axis=-2)   # W S_0 and (e^G Q) S_0: one product

        def step(state, xs):
            wq_n, u_n, p_n, kd_n, decay_n = xs
            ws = _dot("bhrtk,bhrkv->bhrtv", wq_n, state.astype(cd))
            d = (u_n.astype(jnp.float32) - ws[..., :c, :]).astype(cd)
            o = ws[..., c:, :] + _dot("bhrts,bhrsv->bhrtv", p_n, d)
            state = (decay_n[..., None, None] * state
                     + _dot("bhrtk,bhrtv->bhrkv", kd_n, d))
            return state, o.astype(cd)

        chunks_first = lambda x: jnp.moveaxis(x, 3, 0)  # noqa: E731
        state0 = jnp.zeros((b, hk, r, dk, dv), jnp.float32)
        _, o = lax.scan(step, state0, tuple(
            chunks_first(x) for x in (wq, u, p, kd, jnp.exp(g_end))))
        # [N, B, Hk, r, C, Dv] -> [B, S, Hv, Dv]
        o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * c, hv, dv)
    return o[:, :s]
