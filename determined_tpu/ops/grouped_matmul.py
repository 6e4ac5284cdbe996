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
t's j-th choice: choices major). The sort puts the assignments of the
experts held FIRST, so the live rows are the sorted positions below
`n_live` = sum(group_sizes), a number on the device. Both permutations
walk those positions in slabs of `slab_rows` under a `while` whose trip
count is ceil(n_live / slab): they cost the rows routed and not the
buffer, like the matmuls between them. Rows out: a slab of
`h[order % T]` a trip, written in place into a buffer that is allocated
and NOT filled, so rows past the last slab are UNINITIALISED on the way
in as they are undefined on the way out, and the same contract covers
them. Sum back: a slab of sorted rows a trip, widened to float32 and
scatter-added into a [T, D] accumulator by token, positions at or past
`n_live` masked out: that is where the undefined rows stop, forward (the
layer's output) and backward (the gradient of its input). Each is the
other's transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _ragged_dot(x, w, group_sizes):
    return lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))


@jax.custom_vjp
def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """x [M, K] x w [G, K, N] by `group_sizes` [G] int32 -> [M, N] in x's
    dtype (the products accumulate in float32); rows past
    sum(group_sizes) are undefined. Its gradients are `ragged_dot`'s own,
    taken together: the weights' is computed where the rows' is, in the
    layer's backward, and not put off to the end of the step with the
    two [M, .] operands it reads kept until then."""
    return _ragged_dot(x, w, group_sizes)


def _grouped_fwd(x, w, group_sizes):
    return jax.vjp(
        functools.partial(_ragged_dot, group_sizes=group_sizes), x, w)


def _grouped_bwd(back, d_y):
    return (*lax.optimization_barrier(back(d_y)), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


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


def slab_rows(k: int, t: int) -> int:
    """Rows a trip of either permutation moves: static, from the shapes
    alone: one choice's worth of tokens. A slab costs its rows whether
    they are live or masked (the scatter-add ~0.3 ms a trip and ~80 ns a
    row, the gather ~20 ns a row on a v5e), so it should not be much
    longer than what a layer routes here (0.2-2 rows a token where a
    device holds a sixteenth of the experts), nor so short that the
    trips' fixed cost adds up; with every expert held it is k trips over
    the rows one gather moved."""
    return t


def _slabs(n_live: jax.Array, kt: int, slab: int, body, init):
    """`body(start, first, carry)` once a slab of sorted positions
    [start, start + slab) up to `n_live`; positions below `first` belong
    to the slab before (the last slab is pulled back inside the buffer
    where `slab` does not divide it)."""
    def trip(s, carry):
        first = s * slab
        return body(jnp.minimum(first, kt - slab), first, carry)

    return lax.fori_loop(0, (n_live + slab - 1) // slab, trip, init)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rows_of_tokens(h: jax.Array, order: jax.Array, n_live: jax.Array,
                   k: int) -> jax.Array:
    """h [T, D] -> [k*T, D]: row i < `n_live` is token `order[i] % T`
    (assignment `order[i]` of the k*T, in sorted position i); rows from
    the end of the last slab on are uninitialised."""
    t, d = h.shape
    slab = slab_rows(k, t)

    def body(start, _first, rows):
        tokens = lax.dynamic_slice(order, (start,), (slab,)) % t
        return lax.dynamic_update_slice(rows, h[tokens], (start, 0))

    return _slabs(n_live, k * t, slab, body, lax.empty((k * t, d), h.dtype))


def _rows_fwd(h, order, n_live, k):
    return rows_of_tokens(h, order, n_live, k), (order, n_live)


def _rows_bwd(k, res, d_rows):
    order, n_live = res
    return tokens_of_rows(d_rows, order, n_live, k), None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def tokens_of_rows(y: jax.Array, order: jax.Array, n_live: jax.Array,
                   k: int) -> jax.Array:
    """y [k*T, D] in sorted order -> [T, D]: each token's rows below
    `n_live` summed (float32); nothing at or past `n_live` is read into
    the sum."""
    kt, d = y.shape
    t = kt // k
    slab = slab_rows(k, t)

    def body(start, first, acc):
        at = start + jnp.arange(slab, dtype=jnp.int32)
        mine = (at >= first) & (at < n_live)
        rows = lax.dynamic_slice(y, (start, 0), (slab, d))
        tokens = lax.dynamic_slice(order, (start,), (slab,)) % t
        return acc.at[tokens].add(
            jnp.where(mine[:, None], rows.astype(jnp.float32), 0.0))

    return _slabs(n_live, kt, slab, body,
                  jnp.zeros((t, d), jnp.float32)).astype(y.dtype)


def _tokens_fwd(y, order, n_live, k):
    return tokens_of_rows(y, order, n_live, k), (order, n_live)


def _tokens_bwd(k, res, d_tokens):
    order, n_live = res
    return rows_of_tokens(d_tokens, order, n_live, k), None, None


rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)
tokens_of_rows.defvjp(_tokens_fwd, _tokens_bwd)
