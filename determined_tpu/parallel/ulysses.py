"""Ulysses-style sequence parallelism: all-to-all head↔sequence swap.

Net-new vs. the reference (SURVEY.md §2.5). Alternative to ring attention for
long sequences: activations arrive sequence-sharded over the `context` axis;
an all-to-all re-shards them over *heads* so each device runs full-sequence
attention for H/c heads, then a second all-to-all restores sequence sharding.

Tradeoff vs. ring: two all-to-alls of O(B·S·H·D/c) per layer instead of
ring ppermutes; requires num_heads % context_size == 0; attention itself is
unmodified (so any local kernel — including the Pallas flash kernel — drops
in without blockwise accumulation logic).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from determined_tpu.parallel.ring import reference_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "context",
    causal: bool = True,
    local_attn: Optional[Callable] = None,
) -> jax.Array:
    """Call inside shard_map; per-device shapes [B, S/c, H, D].

    Requires H divisible by the context-axis size.
    """
    c = lax.axis_size(axis_name)
    local_attn = local_attn or functools.partial(reference_attention, causal=causal)
    if c == 1:
        return local_attn(q, k, v)

    def seq_to_heads(x):
        # [B, S/c, H, D] -> [B, S, H/c, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    out = local_attn(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v))
    return heads_to_seq(out)


def make_ulysses_attention(
    mesh: Mesh,
    *,
    causal: bool = True,
    batch_axes=("data", "fsdp"),
    seq_axis: str = "context",
):
    spec = P(batch_axes, seq_axis, None, None)
    fn = functools.partial(ulysses_attention, axis_name=seq_axis, causal=causal)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )
