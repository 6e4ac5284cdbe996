"""Grouped matmul over the experts a device holds, and the row
permutations around it.

`grouped_matmul(x, w, group_sizes)`: rows of x [M, K] sorted by group;
group e owns the next `group_sizes[e]` rows and multiplies them by w[e]
[K, N]. It is `jax.lax.ragged_dot`, which XLA's TPU compiler lowers to
a Mosaic grouped GEMM of its own whose grid is sized by the rows the
groups hold at run time (`ragged-dot-metadata` in the compiled text), so
a buffer sized for the worst case costs the rows actually routed and not
the buffer. Rows past the last group belong to no expert and what they
hold afterwards is UNDEFINED (the kernel does not visit them; the
reference lowering leaves zeros): nothing may read them.

`rows_of_tokens` / `tokens_of_rows` move between tokens [T, D] and their
k assignment rows [k*T, D] sorted by expert (assignment j*T + t is token
t's j-th choice: choices major, so that summing a token's k rows is k
slabs of [T, D] added and not a [T, k, D] array, whose second-minor
dimension of k would be padded and copied on the chip). Each is the other's
transpose, and both are gathers (by `order` one way, by its inverse the
other), so neither pass has a scatter-add. `tokens_of_rows` takes which
assignments are `live` (routed to an expert held) and reads nothing of
the others: that is where the undefined rows stop, forward (the layer's
output) and backward (the gradient of its input).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """x [M, K] x w [G, K, N] by `group_sizes` [G] int32 -> [M, N] in x's
    dtype (the products accumulate in float32); rows past
    sum(group_sizes) are undefined."""
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))


def grouped_matmul_loop(x: jax.Array, w: jax.Array,
                        group_sizes: jax.Array) -> jax.Array:
    """The same result as a Python loop over the groups, one masked dense
    matmul each: what the tests hold `grouped_matmul` to."""
    ends = jnp.cumsum(group_sizes)
    rows = jnp.arange(x.shape[0])
    out = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
    for e in range(w.shape[0]):
        mine = (rows >= ends[e] - group_sizes[e]) & (rows < ends[e])
        out = out + jnp.where(mine[:, None], jnp.dot(
            x, w[e], preferred_element_type=jnp.float32), 0.0)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def rows_of_tokens(h: jax.Array, order: jax.Array, inverse: jax.Array,
                   live: jax.Array, k: int) -> jax.Array:
    """h [T, D] -> [k*T, D]: row i is token `order[i] % T` (assignment
    `order[i]` of the k*T, in sorted position i). `live` [k*T] bool, by
    assignment, is for the way back."""
    return h[order % h.shape[0]]


def _rows_fwd(h, order, inverse, live, k):
    return h[order % h.shape[0]], (order, inverse, live)


def _rows_bwd(k, res, d_rows):
    order, inverse, live = res
    return tokens_of_rows(d_rows, order, inverse, live, k), None, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def tokens_of_rows(y: jax.Array, order: jax.Array, inverse: jax.Array,
                   live: jax.Array, k: int) -> jax.Array:
    """y [k*T, D] in sorted order -> [T, D]: each token's live rows
    summed (float32); `inverse` is the inverse permutation of `order`."""
    t = y.shape[0] // k
    rows = jnp.where(live[:, None], y[inverse].astype(jnp.float32), 0.0)
    return jnp.sum(rows.reshape(k, t, -1), axis=0).astype(y.dtype)


def _tokens_fwd(y, order, inverse, live, k):
    return tokens_of_rows(y, order, inverse, live, k), (order, inverse, live)


def _tokens_bwd(k, res, d_tokens):
    order, inverse, live = res
    # (dead rows get their token's cotangent too: nothing reads it back)
    return (rows_of_tokens(d_tokens, order, inverse, live, k),
            None, None, None)


rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)
tokens_of_rows.defvjp(_tokens_fwd, _tokens_bwd)
