"""The system's expert layer: top-k routing over all experts, dropless,
for the experts this device holds.

A device under expert parallelism holds `n_held` of the layer's
`n_routed` experts (a contiguous range from `first_expert`). The layer
routes every token over ALL `n_routed` (the router keeps its published
width), keeps the k best (by score, or by score plus a selection `bias`
that takes part in the choice alone), normalises their weights over all
k chosen (if `normalize`), scales them (`scale`), and computes the part
of the result its own experts give:

    y = sum_{e in top-k and held} w_e SwiGLU_e(h)   [+ the shared expert]

What the absent experts would add is left out; on one device there is no
exchange and nothing stands in for it. With all experts held it is the
uncut layer. No token is dropped: the k*T assignments are sorted by
expert (those of experts not held last, so the live rows are the first
`n_live` sorted positions), the rows go through one grouped matmul a
projection over the experts held (`ops/grouped_matmul.py`), and each
token's rows are summed back. The row buffer is sized for the worst case
(k*T rows: every choice of every token held here) and is never filled:
past the live rows it is UNINITIALISED going into the matmuls and
undefined coming out of them, and nothing reads it there. The grouped
matmuls' cost and the two permutations' (slabs of the live rows under a
`while`) follow the rows actually routed; the elementwise passes between
the matmuls still follow the buffer.

Scopes (`models/base.py`, `INNER_SCOPES`): `moe_route` (router, top-k,
sort, the two row permutations), `moe_experts` (the grouped matmuls and
the activation between them), `moe_shared`.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from determined_tpu.ops.grouped_matmul import (
    grouped_matmul,
    rows_of_tokens,
    tokens_of_rows,
)


def swiglu(h: jax.Array, w_in: jax.Array, w_out: jax.Array) -> jax.Array:
    """(SiLU(h W_gate) * h W_up) W_down; w_in [D, 2, F] holds gate and up."""
    gu = jnp.einsum("td,dgf->tgf", h, w_in)
    return jnp.dot(jax.nn.silu(gu[:, 0]) * gu[:, 1], w_out)


def softmax_scores(logits: jax.Array) -> jax.Array:
    return jax.nn.softmax(logits, axis=-1)


def sigmoid_scores(logits: jax.Array) -> jax.Array:
    """An expert's score on its own, not against the others'."""
    return jax.nn.sigmoid(logits)


def expert_layer(
    h: jax.Array,
    router: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    top_k: int,
    first_expert: int = 0,
    normalize: bool = True,
    score: Callable[[jax.Array], jax.Array] = softmax_scores,
    bias: Optional[jax.Array] = None,
    scale: float = 1.0,
    norm_eps: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """h [T, D] (compute dtype); router [D, n_routed] (float32 master);
    w_in [n_held, D, 2, F] and w_out [n_held, F, D] (compute dtype).
    `bias` [n_routed]: a selection bias, added to the scores for the
    CHOICE of the k experts and for nothing else (the weights are the
    scores'; no gradient reaches it). `scale` multiplies the k weights
    after their normalisation, `norm_eps` guards its divisor.
    -> (y [T, D], counters): `held_rows` (rows routed to held experts,
    a scalar) and `load_max_over_mean` over the held experts."""
    t, d = h.shape
    n_held, f = w_in.shape[0], w_in.shape[-1]
    with jax.named_scope("moe_route"):
        # float32 and full precision: a choice between two experts must
        # not turn on a bf16 rounding of the scores.
        p = score(jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST))
        if bias is None:
            top_p, top_e = lax.top_k(p, top_k)                 # [T, k]
        else:
            _, top_e = lax.top_k(
                p + lax.stop_gradient(bias.astype(jnp.float32)), top_k)
            top_p = jnp.take_along_axis(p, top_e, axis=-1)
        if normalize:
            total = jnp.sum(top_p, axis=-1, keepdims=True)
            top_p = top_p / (total + norm_eps if norm_eps else total)
        if scale != 1.0:
            top_p = top_p * scale
        # assignments choice-major: j*T + t (`ops/grouped_matmul.py`)
        local = top_e.T.reshape(-1) - first_expert
        live = (local >= 0) & (local < n_held)                 # [k*T]
        key = jnp.where(live, local, n_held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(
            jnp.int32)
        # (the `where` also keeps what a dead row holds out of the
        # router's gradient)
        weight = jnp.where(live, top_p.T.reshape(-1), 0.0)[order].astype(
            h.dtype)
        n_live = jnp.sum(group_sizes)   # the live rows sort first
        rows = rows_of_tokens(h, order, n_live, top_k)         # [k*T, D]
    with jax.named_scope("moe_experts"):
        # Rows past the held experts' are uninitialised coming in and
        # undefined from here on (`grouped_matmul`), until
        # `tokens_of_rows` leaves them out.
        gu = grouped_matmul(rows, w_in.reshape(n_held, d, 2 * f), group_sizes)
        # the combine weight rides the narrow activations (F wide), not
        # the layer's output (D wide): w (a W_down) = (w a) W_down
        act = jax.nn.silu(gu[:, :f]) * gu[:, f:] * weight[:, None]
        y = grouped_matmul(act, w_out, group_sizes)
    with jax.named_scope("moe_route"):
        y = tokens_of_rows(y, order, n_live, top_k)
        held = n_live.astype(jnp.float32)
        counters = {
            "held_rows": held,
            "load_max_over_mean": jnp.max(group_sizes).astype(jnp.float32)
            * n_held / jnp.maximum(held, 1.0),
        }
    return y, counters


def shared_expert(h: jax.Array, gate: Optional[jax.Array], w_in: jax.Array,
                  w_out: jax.Array) -> jax.Array:
    """sigmoid(h . gate) * SwiGLU(h), the expert every token goes through
    (every device computes it alike: it counts once); gate [D] or None."""
    with jax.named_scope("moe_shared"):
        y = swiglu(h, w_in, w_out)
        if gate is not None:
            y = y * jax.nn.sigmoid(jnp.dot(
                h, gate.astype(h.dtype),
                preferred_element_type=jnp.float32))[:, None].astype(h.dtype)
        return y


def on_batch_shards(
    local: Callable[[jax.Array, Any], Tuple[jax.Array, jax.Array]],
    h: jax.Array, w: Any, mesh: Optional[Mesh], batch_axes: Tuple[str, ...],
) -> Tuple[jax.Array, jax.Array]:
    """`local(h [b, S, D], w) -> (y, counters [1, n])` on every batch
    shard of h with its own copy of `w`: each shard routes its own tokens
    through the experts held (no exchange), and the counters are the
    shards' means. One call where the batch is not sharded."""
    shards = 1
    if mesh is not None:
        for axis in batch_axes:
            shards *= mesh.shape.get(axis, 1)
    if shards == 1:
        y, counters = local(h, w)
    else:
        spec = P(batch_axes)
        y, counters = shard_map(
            local, mesh=mesh,
            in_specs=(spec, jax.tree.map(lambda _: P(), w)),
            out_specs=(spec, spec), check_vma=False)(h, w)
    return y, jnp.mean(counters, axis=0)
