"""Attention dispatch: pick the right kernel for the current mesh layout.

The reference had no attention code of its own (it lived in torch/DeepSpeed
kernels); here the model calls one entry point and the layout decides:

- ``context`` axis sharded (> 1): ring attention — K/V rotate over ICI via
  ppermute while each device attends for its local sequence chunk
  (determined_tpu.parallel.ring).
- otherwise on TPU: the Pallas flash kernel (determined_tpu.ops), wrapped in
  shard_map because pallas_call is opaque to the GSPMD partitioner — batch
  splits over data/fsdp, heads over tensor.
- otherwise (CPU tests, tiny shapes): plain einsum softmax attention, which
  XLA partitions on its own.

All paths take/return [B, S, H, D] and are numerically exact. Masking
(causal, sliding `window`, packed-sequence `segment_ids`) is one model
shared by dense/flash/ring — see ops/flash_attention.py; ulysses re-gathers
the full sequence per head subset and supports the causal mask only.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from determined_tpu.ops.flash_attention import (
    fit_block,
    flash_attention,
    flash_attention_qkv,
)
from determined_tpu.parallel.ring import reference_attention, ring_attention

BATCH_AXES = ("data", "fsdp")


def _resolve_impl(impl: str, mesh: Optional[Mesh], seq: int) -> str:
    if impl != "auto":
        return impl
    if mesh is not None and mesh.shape.get("context", 1) > 1:
        return "ring"
    if jax.default_backend() == "tpu" and seq % 128 == 0:
        return "flash"
    return "dense"


def _check_impl(impl: str, layout: str, window, segment_ids) -> None:
    """What an implementation cannot do, for both dispatchers below."""
    if layout == "zigzag" and impl != "ring":
        raise ValueError(
            "layout='zigzag' requires ring attention (a sharded context "
            f"axis); resolved impl is {impl!r} — dense/flash causal masks "
            "assume contiguous order, and ulysses re-gathers the full "
            "sequence: either would be silently wrong"
        )
    if impl == "ulysses" and (window is not None or segment_ids is not None):
        raise ValueError(
            "window/segment_ids are not supported with ulysses "
            "attention; use ring (sharded context) or flash/dense"
        )


def _flash(ops, *, mesh, causal, block_q, block_k, window, segment_ids):
    """The flash kernels over `ops`: (q, k, v), each [B, S, H, D], or the
    one-tuple (qkv,) [B, S, 3, H, D] of a fused projection."""
    fused = len(ops) == 1
    kernel = flash_attention_qkv if fused else flash_attention
    # Fit the tuned block sizes to this sequence (block | seq is a hard
    # kernel requirement; a 1024-tuned block must degrade, not raise,
    # for a 1536-long sequence).
    block_q = fit_block(ops[0].shape[1], block_q)
    block_k = fit_block(ops[-1].shape[1], block_k)

    def local(*args):  # the operands, then the segment ids if there are any
        return kernel(
            *args[:len(ops)], causal=causal, block_q=block_q,
            block_k=block_k, window=window,
            segment_ids=args[len(ops)] if len(args) > len(ops) else None,
        )

    args = ops if segment_ids is None else (*ops, segment_ids)
    if mesh is None:
        out = local(*args)
    else:
        spec = P(BATCH_AXES, None, "tensor", None)
        in_spec = P(BATCH_AXES, None, None, "tensor", None) if fused else spec
        seg_specs = () if segment_ids is None else (P(BATCH_AXES, None),)
        out = shard_map(
            local, mesh=mesh, in_specs=(in_spec,) * len(ops) + seg_specs,
            out_specs=spec, check_vma=False,
        )(*args)
    # Remat boundary marker: "dots saveable" policies don't recognize a
    # pallas_call as a dot, so without this name the whole flash forward
    # re-runs inside the backward (models/gpt.py combines the dots
    # policy with save_only_these_names("flash_out")).
    return checkpoint_name(out, "flash_out")


def attention_qkv(
    qkv: jax.Array,
    *,
    mesh: Optional[Mesh] = None,
    causal: bool = True,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
    layout: str = "contiguous",
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """`attention` of a fused projection qkv [B, S, 3, H, D] (self
    attention: one sequence). The flash path hands the kernels the one
    array, which they read in place where they can
    (`ops.flash_attention.flash_attention_qkv`); every other impl gets
    the three slices."""
    impl = _resolve_impl(impl, mesh, qkv.shape[1])
    if impl == "flash" and layout != "zigzag":
        return _flash(
            (qkv,), mesh=mesh, causal=causal, block_q=block_q,
            block_k=block_k, window=window, segment_ids=segment_ids,
        )
    return attention(
        qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], mesh=mesh, causal=causal,
        impl=impl, block_q=block_q, block_k=block_k, layout=layout,
        window=window, segment_ids=segment_ids,
    )


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Optional[Mesh] = None,
    causal: bool = True,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
    layout: str = "contiguous",
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Multi-head attention over [B, S, H, D] tensors.

    impl: "auto" | "dense" | "flash" | "ring". "auto" selects ring when the
    mesh's context axis is sharded, flash on TPU, dense elsewhere.
    block_q/block_k: flash kernel tile sizes, fitted down to divisors of the
    sequence as needed. GPTConfig sets these (1024/1024: equal to GPT-2's
    sequence, which selects the monolithic kernels; or the autotuner's
    probed winner with flash_autotune on); 512 is a neutral default for
    direct callers.
    layout: "zigzag" = the sequence dim is ALREADY in zigzag device order
    (data/tokens.py native emission) — only the ring impl understands that
    placement, and it then runs gather-free.
    window: sliding-window size (causal only) — the kernels skip blocks
    (compute + DMA) outside the band, and the ring stops rotating K/V past
    the window's reach.
    segment_ids: [B, S] int ids for packed sequences; attention only
    within equal ids.
    """
    impl = _resolve_impl(impl, mesh, q.shape[1])
    _check_impl(impl, layout, window, segment_ids)

    if impl == "dense":
        return reference_attention(
            q, k, v, causal=causal, window=window, segment_ids=segment_ids
        )

    if impl == "flash":
        return _flash(
            (q, k, v), mesh=mesh, causal=causal, block_q=block_q,
            block_k=block_k, window=window, segment_ids=segment_ids,
        )

    if impl == "ring":
        if mesh is None:
            raise ValueError("ring attention needs a mesh")
        # Contiguous layout: make_ring_attention permutes in/out around the
        # balanced-causal kernel (a gather each way). Zigzag layout: the
        # data pipeline already emitted zigzag order (data/tokens.py
        # zigzag_ring) and the kernel runs gather-free. Tuned blocks and
        # window/segment args ride into every per-hop flash call.
        from determined_tpu.parallel.ring import make_ring_attention

        return make_ring_attention(
            mesh, causal=causal, data_layout=layout,
            block_q=block_q, block_k=block_k, window=window,
        )(q, k, v, segment_ids)

    if impl == "ulysses":
        # All-to-all head<->sequence swap: each device runs full-sequence
        # attention for H/(tensor*context) heads
        # (determined_tpu.parallel.ulysses). Heads stay sharded over tensor
        # like the other impls — omitting it would silently replicate
        # activations across the tensor axis.
        if mesh is None:
            raise ValueError("ulysses attention needs a mesh")
        ctx = mesh.shape.get("context", 1)
        tp = mesh.shape.get("tensor", 1)
        local_heads = q.shape[2] // max(tp, 1)
        if q.shape[2] % max(tp, 1) != 0 or local_heads % max(ctx, 1) != 0:
            raise ValueError(
                f"ulysses needs heads ({q.shape[2]}) divisible by "
                f"tensor ({tp}) and heads/tensor ({local_heads}) divisible "
                f"by the context axis ({ctx})"
            )
        from determined_tpu.parallel.ulysses import ulysses_attention

        spec = P(BATCH_AXES, "context", "tensor", None)

        def local(q_, k_, v_):
            return ulysses_attention(q_, k_, v_, axis_name="context", causal=causal)

        return shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    raise ValueError(f"unknown attention impl {impl!r}")


def attention_manual(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Optional[Mesh] = None,
    causal: bool = True,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
    layout: str = "contiguous",
    window: Optional[int] = None,
) -> jax.Array:
    """`attention` from INSIDE a shard_map manual region (a pipeline
    stage, parallel/pipeline.py): q/k/v are the local shards and no
    shard_map may be nested, so the sequence-parallel impls are called
    per shard over the manual ``context`` axis and nothing else is left
    but dense.

    With a sharded context axis the pipeline's shard_map is manual on
    BOTH axes and each stage attends over its sequence shard directly:
    ring by default (and mandatory for zigzag layouts), ulysses when
    `impl` names it. Without one: dense (contiguous order only).
    """
    ctx = mesh.shape.get("context", 1) if mesh is not None else 1
    if ctx == 1:
        impl = "dense"
    elif impl != "ulysses":
        impl = "ring"
    _check_impl(impl, layout, window, None)
    if impl == "dense":
        return reference_attention(q, k, v, causal=causal, window=window)
    if impl == "ulysses":
        from determined_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, axis_name="context", causal=causal)
    return ring_attention(
        q, k, v, axis_name="context", causal=causal, block_q=block_q,
        block_k=block_k, window=window, layout=layout,
    )
