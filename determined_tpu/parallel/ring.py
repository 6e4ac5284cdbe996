"""Ring attention: exact attention over a sequence-sharded `context` axis.

Net-new vs. the reference, which had no sequence/context parallelism at all
(SURVEY.md §2.5: "Absent — no hits for ring/ulysses/sequence-parallel").
Design follows the Ring Attention pattern: each device owns one sequence
chunk of Q/K/V; K/V chunks rotate around the ring via `ppermute` while every
device merges blockwise-softmax partials for its Q chunk (numerically exact,
not approximate).

Three properties matter for TPU throughput:

- the per-block inner attention is the Pallas flash kernel
  (`determined_tpu.ops.flash_attention.flash_attention_lse`), so every ring
  step runs fused MXU attention with fp32 accumulation — not an einsum that
  materializes [B, H, Sq, Sk] scores;
- with `layout="zigzag"` each device owns global chunks (i, 2R−1−i), which
  makes causal work *identical* on every ring step and every device (2
  half-chunk attends per step); the naive contiguous layout leaves device
  R−1 doing R× the work of device 0 and forces compute-then-discard steps;
- steps (or step-parts) that cannot contribute are skipped via `lax.switch`
  on the kv chunk's origin, not computed-and-masked.

Masking composes with the kernel's band/segment model:

- `segment_ids` (packed sequences) ride the ring: the kv chunk's ids
  rotate alongside K/V and every per-hop flash call masks q-ids against
  the received kv-ids;
- `window` (sliding window, causal, contiguous layout): each cross-device
  hop is a plain kernel call with `kv_offset = hop·S_local` (the static
  global offset between the q chunk and the received kv chunk), and hops
  whose whole chunk lies outside the window are not emitted at all — a
  W-token window stops rotating K/V after ceil-ish (W+L−1)/L hops, so
  communication scales with the window, not the sequence.

Communication rides ICI neighbor links (ppermute), overlapping with the
per-step attention compute; peak memory is O(S_local·block) per step instead
of O(S²) — this is what makes million-token contexts feasible on a pod.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from determined_tpu.ops.flash_attention import fit_block, flash_attention_lse


# ---------------------------------------------------------------------------
# Zigzag chunk placement
# ---------------------------------------------------------------------------
def zigzag_indices(seq_len: int, ring_size: int) -> np.ndarray:
    """Permutation taking contiguous global order → zigzag device order.

    The sequence splits into 2R chunks; device i owns chunks (i, 2R−1−i)
    concatenated. Under a causal mask this balances work exactly: at every
    ring step each device attends two half-chunks' worth of keys (one full,
    or the diagonal's two triangles), instead of device i doing i+1 steps
    of useful work.
    """
    if seq_len % (2 * ring_size):
        raise ValueError(
            f"zigzag needs seq_len ({seq_len}) divisible by 2*ring ({2 * ring_size})"
        )
    chunk = seq_len // (2 * ring_size)
    order = []
    for i in range(ring_size):
        order.extend(range(i * chunk, (i + 1) * chunk))
        j = 2 * ring_size - 1 - i
        order.extend(range(j * chunk, (j + 1) * chunk))
    return np.asarray(order, dtype=np.int32)


def inverse_permutation(perm: np.ndarray) -> np.ndarray:
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm), dtype=perm.dtype)
    return inv


# ---------------------------------------------------------------------------
# Partial-softmax merge
# ---------------------------------------------------------------------------
def _merge(acc, lse_run, o_p, lse_p):
    """Fold a normalized partial (o_p, lse_p) into the running (acc, lse).

    acc/lse_run: fp32 [.., S, H, D] / [.., S, H]; the merge weight
    exp(lse_i − lse_total) is the standard blockwise-softmax combination —
    exact, and differentiable end to end (lse_p carries a cotangent back
    into the flash kernel's backward).
    """
    lse_new = jnp.logaddexp(lse_run, lse_p)
    # Slots nothing has touched yet have lse_run = lse_new = −inf; the
    # subtraction would be NaN. They contribute weight 0 either way.
    # (Fully-masked rows from the kernel come back at ≈ −1e30, which is
    # finite — exp(−1e30 − safe) underflows to the same weight 0.)
    safe = jnp.where(jnp.isneginf(lse_new), 0.0, lse_new)
    w_old = jnp.where(jnp.isneginf(lse_run), 0.0, jnp.exp(lse_run - safe))
    w_new = jnp.where(jnp.isneginf(lse_p), 0.0, jnp.exp(lse_p - safe))
    acc_new = acc * w_old[..., None] + o_p.astype(jnp.float32) * w_new[..., None]
    return acc_new, lse_new


# ---------------------------------------------------------------------------
# Core (per-shard, call inside shard_map)
# ---------------------------------------------------------------------------
def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "context",
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    layout: str = "contiguous",
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact attention with Q/K/V sequence-sharded over `axis_name`.

    Call inside shard_map. Shapes per device: [B, S_local, H, D];
    `segment_ids` (optional) is the per-shard [B, S_local] id slice.

    layout="contiguous" (default): devices hold consecutive chunks in
    axis-index order — the safe contract for arbitrary callers; causal work
    is imbalanced across ranks. `window` (sliding window) is supported on
    this layout only, and prunes both compute and K/V rotation to the hops
    the window can reach.
    layout="zigzag" (causal only): each device holds global chunks
    (i, 2R−1−i) — see `zigzag_indices` — which balances causal work
    exactly. Opt-in because feeding contiguous data to the zigzag math
    would be silently wrong; `make_ring_attention` applies the permutation
    for global arrays, data loaders should emit it directly. Window
    masking is not expressible with static offsets in this interleaved
    placement — windowed zigzag raises.
    """
    ring_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring layout {layout!r}")
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window) requires causal=True")
        if layout == "zigzag":
            raise ValueError(
                "window is supported with layout='contiguous' only: zigzag "
                "interleaves two global chunks per device, so a hop's "
                "q↔kv offset isn't a single static kv_offset"
            )
    has_segs = segment_ids is not None
    qseg = segment_ids

    def flash(q_, k_, v_, *, causal, window=None, kv_offset=0, qseg=None,
              kseg=None):
        # Flash requires block | seq; shrink to the largest divisor so any
        # (even) local length works — the einsum ring this replaced had no
        # length constraint, and per-call lengths here include half-chunks.
        bq = fit_block(q_.shape[1], block_q)
        bk = fit_block(k_.shape[1], block_k)
        return flash_attention_lse(
            q_, k_, v_, causal=causal, scale=scale, block_q=bq, block_k=bk,
            window=window, kv_offset=kv_offset,
            segment_ids=qseg, kv_segment_ids=kseg,
        )

    if ring_size == 1:
        o, _ = flash(
            q, k, v, causal=causal, window=window, qseg=qseg, kseg=qseg
        )
        return o

    acc0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((b, s_local, h), -jnp.inf, jnp.float32)
    perm = [(i, (i + 1) % ring_size) for i in range(ring_size)]

    def rotate(x):
        return lax.ppermute(x, axis_name, perm)

    if not causal:
        # Every step attends the full received chunk; layout is irrelevant.
        def step(carry, _):
            if has_segs:
                k_cur, v_cur, kseg_cur, acc, lse_run = carry
            else:
                k_cur, v_cur, acc, lse_run = carry
                kseg_cur = None
            o_p, lse_p = flash(
                q, k_cur, v_cur, causal=False, qseg=qseg, kseg=kseg_cur
            )
            acc, lse_run = _merge(acc, lse_run, o_p, lse_p)
            nxt = (rotate(k_cur), rotate(v_cur))
            if has_segs:
                nxt += (rotate(kseg_cur),)
            return nxt + (acc, lse_run), None

        init = (k, v, qseg, acc0, lse0) if has_segs else (k, v, acc0, lse0)
        carry, _ = lax.scan(step, init, None, length=ring_size)
        acc = carry[-2]
        return acc.astype(q.dtype)

    if causal and window is not None:
        # Sliding window, contiguous layout: hop s attends the kv chunk
        # sitting s·L tokens behind — a static kv_offset, so each hop is a
        # plain kernel call and the band machinery skips dead blocks
        # inside it. Hops with s·L ≥ W + L − 1 can't reach the window for
        # ANY row and are not emitted: K/V stop rotating after the last
        # reachable hop (communication scales with W, not S).
        hops = min(ring_size, (window + s_local - 2) // s_local + 1)
        acc, lse_run = acc0, lse0
        k_cur, v_cur, kseg_cur = k, v, qseg
        for s_hop in range(hops):
            if s_hop == 0:
                o_p, lse_p = flash(
                    q, k_cur, v_cur, causal=True, window=window,
                    qseg=qseg, kseg=kseg_cur,
                )
                acc, lse_run = _merge(acc, lse_run, o_p, lse_p)
            else:
                def attend(acc_, lse_, k_=k_cur, v_=v_cur, kseg_=kseg_cur,
                           off=s_hop * s_local):
                    o_p, lse_p = flash(
                        q, k_, v_, causal=True, window=window,
                        kv_offset=off, qseg=qseg, kseg=kseg_,
                    )
                    return _merge(acc_, lse_, o_p, lse_p)

                # Ranks with fewer than s_hop predecessors received a
                # wrapped (future) chunk: skip it.
                acc, lse_run = lax.cond(
                    s_hop <= my_idx, attend, lambda a, l: (a, l),
                    acc, lse_run,
                )
            if s_hop + 1 < hops:
                k_cur, v_cur = rotate(k_cur), rotate(v_cur)
                if has_segs:
                    kseg_cur = rotate(kseg_cur)
        return acc.astype(q.dtype)

    if layout == "zigzag":
        if s_local % 2:
            raise ValueError("zigzag layout needs an even local sequence")
        c = s_local // 2
        qseg1 = qseg[:, :c] if has_segs else None
        qseg2 = qseg[:, c:] if has_segs else None

        def kseg_halves(kseg_cur):
            if not has_segs:
                return None, None
            return kseg_cur[:, :c], kseg_cur[:, c:]

        def diag(k_cur, v_cur, kseg_cur, acc, lse_run):
            # Own chunks (i, 2R−1−i): q1·k1 and q2·k2 are causal triangles,
            # q2·k1 is a full block (chunk 2R−1−i is strictly after chunk i).
            q1, q2 = q[:, :c], q[:, c:]
            k1, k2 = k_cur[:, :c], k_cur[:, c:]
            v1, v2 = v_cur[:, :c], v_cur[:, c:]
            kseg1, kseg2 = kseg_halves(kseg_cur)
            o11, l11 = flash(q1, k1, v1, causal=True, qseg=qseg1, kseg=kseg1)
            o21, l21 = flash(q2, k1, v1, causal=False, qseg=qseg2, kseg=kseg1)
            o22, l22 = flash(q2, k2, v2, causal=True, qseg=qseg2, kseg=kseg2)
            acc1, lse1 = _merge(acc[:, :c], lse_run[:, :c], o11, l11)
            acc2, lse2 = _merge(acc[:, c:], lse_run[:, c:], o21, l21)
            acc2, lse2 = _merge(acc2, lse2, o22, l22)
            return (
                jnp.concatenate([acc1, acc2], axis=1),
                jnp.concatenate([lse1, lse2], axis=1),
            )

        def kv_before(k_cur, v_cur, kseg_cur, acc, lse_run):
            # kv from rank j < i: its first chunk (j) precedes both of ours
            # → full attend; its second (2R−1−j) follows both → skip.
            kseg1, _ = kseg_halves(kseg_cur)
            o_p, lse_p = flash(
                q, k_cur[:, :c], v_cur[:, :c], causal=False,
                qseg=qseg, kseg=kseg1,
            )
            return _merge(acc, lse_run, o_p, lse_p)

        def kv_after(k_cur, v_cur, kseg_cur, acc, lse_run):
            # kv from rank j > i: both its chunks precede our second chunk
            # (j < 2R−1−i and 2R−1−j < 2R−1−i) and follow our first → only
            # q2 attends, against the whole received kv.
            o_p, lse_p = flash(
                q[:, c:], k_cur, v_cur, causal=False,
                qseg=qseg2, kseg=kseg_cur if has_segs else None,
            )
            acc2, lse2 = _merge(acc[:, c:], lse_run[:, c:], o_p, lse_p)
            return (
                jnp.concatenate([acc[:, :c], acc2], axis=1),
                jnp.concatenate([lse_run[:, :c], lse2], axis=1),
            )

        branches = (diag, kv_before, kv_after)

        def step(carry, step_idx):
            if has_segs:
                k_cur, v_cur, kseg_cur, acc, lse_run = carry
            else:
                k_cur, v_cur, acc, lse_run = carry
                kseg_cur = None
            kv_idx = (my_idx - step_idx) % ring_size
            case = jnp.where(kv_idx == my_idx, 0, jnp.where(kv_idx < my_idx, 1, 2))
            acc, lse_run = lax.switch(
                case, branches, k_cur, v_cur, kseg_cur, acc, lse_run
            )
            nxt = (rotate(k_cur), rotate(v_cur))
            if has_segs:
                nxt += (rotate(kseg_cur),)
            return nxt + (acc, lse_run), None

        init = (k, v, qseg, acc0, lse0) if has_segs else (k, v, acc0, lse0)
        carry, _ = lax.scan(step, init, jnp.arange(ring_size))
        return carry[-2].astype(q.dtype)

    # Contiguous causal: chunk j contributes fully when j < i, triangularly
    # when j == i, never when j > i (skipped — the pre-r2 code computed and
    # discarded those steps). Load stays imbalanced across ranks; prefer
    # zigzag when the data layout allows.
    def c_diag(k_cur, v_cur, kseg_cur, acc, lse_run):
        o_p, lse_p = flash(
            q, k_cur, v_cur, causal=True, qseg=qseg,
            kseg=kseg_cur if has_segs else None,
        )
        return _merge(acc, lse_run, o_p, lse_p)

    def c_before(k_cur, v_cur, kseg_cur, acc, lse_run):
        o_p, lse_p = flash(
            q, k_cur, v_cur, causal=False, qseg=qseg,
            kseg=kseg_cur if has_segs else None,
        )
        return _merge(acc, lse_run, o_p, lse_p)

    def c_skip(k_cur, v_cur, kseg_cur, acc, lse_run):
        return acc, lse_run

    branches = (c_diag, c_before, c_skip)

    def step(carry, step_idx):
        if has_segs:
            k_cur, v_cur, kseg_cur, acc, lse_run = carry
        else:
            k_cur, v_cur, acc, lse_run = carry
            kseg_cur = None
        kv_idx = (my_idx - step_idx) % ring_size
        case = jnp.where(kv_idx == my_idx, 0, jnp.where(kv_idx < my_idx, 1, 2))
        acc, lse_run = lax.switch(
            case, branches, k_cur, v_cur, kseg_cur, acc, lse_run
        )
        nxt = (rotate(k_cur), rotate(v_cur))
        if has_segs:
            nxt += (rotate(kseg_cur),)
        return nxt + (acc, lse_run), None

    init = (k, v, qseg, acc0, lse0) if has_segs else (k, v, acc0, lse0)
    carry, _ = lax.scan(step, init, jnp.arange(ring_size))
    return carry[-2].astype(q.dtype)


# ---------------------------------------------------------------------------
# Global-array wrapper
# ---------------------------------------------------------------------------
def make_ring_attention(
    mesh: Mesh,
    *,
    causal: bool = True,
    batch_axes=("data", "fsdp"),
    seq_axis: str = "context",
    heads_axis: str = "tensor",
    zigzag: Optional[bool] = None,
    block_q: int = 512,
    block_k: int = 512,
    data_layout: str = "contiguous",
    window: Optional[int] = None,
):
    """shard_map ring_attention over the mesh, on global [B, S, H, D] arrays.

    Returns a callable `(q, k, v, segment_ids=None) -> o`; `segment_ids`
    is the global [B, S] id array for packed sequences.

    With zigzag (default for causal, unless a window forces contiguous)
    the global sequence is permuted into zigzag device order before the
    shard_map and the output permuted back — convenient for tests and
    ad-hoc use. Training input pipelines should instead emit tokens in
    zigzag order (data/tokens.py `zigzag_ring`) and keep the whole model
    in that order — pass data_layout="zigzag" and the kernel runs with NO
    permute gathers (the contiguous wrapper pays one each way at the jit
    boundary).
    """
    if zigzag is None:
        # Zigzag balances causal work, but window masking needs the
        # contiguous placement's static offsets.
        zigzag = causal and window is None
    ring = mesh.shape.get(seq_axis, 1)
    spec = P(batch_axes, seq_axis, heads_axis, None)
    seg_spec = P(batch_axes, seq_axis)

    _mapped_cache = {}

    def mapped(layout, with_segs):
        # Built once per (layout, with_segs) for the RETURNED callable, so
        # a caller that holds it (tests, a captured closure) reuses one
        # shard_map object across eager invocations. The models/attention
        # dispatcher constructs a fresh make_ring_attention per call — its
        # real path runs under the caller's jit, where tracing happens
        # once at that boundary regardless.
        key = (layout, with_segs)
        if key in _mapped_cache:
            return _mapped_cache[key]
        fn = functools.partial(
            ring_attention,
            axis_name=seq_axis,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            layout=layout,
            window=window,
        )
        if with_segs:
            def with_seg_fn(q, k, v, seg):
                return fn(q, k, v, segment_ids=seg)

            out = shard_map(
                with_seg_fn, mesh=mesh,
                in_specs=(spec, spec, spec, seg_spec), out_specs=spec,
                check_vma=False,
            )
        else:
            out = shard_map(
                fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )
        _mapped_cache[key] = out
        return out

    def call(layout, q, k, v, segment_ids=None):
        if segment_ids is not None:
            return mapped(layout, True)(q, k, v, segment_ids)
        return mapped(layout, False)(q, k, v)

    if data_layout == "zigzag":
        # The caller's arrays are ALREADY in zigzag device order (native
        # emission); run the balanced-causal kernel directly, gather-free.
        if not causal or ring <= 1:
            raise ValueError(
                "data_layout='zigzag' needs causal attention and a sharded "
                f"context axis (ring={ring})"
            )
        if window is not None:
            raise ValueError(
                "window needs the contiguous ring layout (static per-hop "
                "offsets); emit contiguous data or drop the window"
            )
        return functools.partial(call, "zigzag")

    if not (zigzag and causal and ring > 1):
        return functools.partial(call, "contiguous")

    def wrapper(q, k, v, segment_ids=None):
        s = q.shape[1]
        if s % (2 * ring):
            # Sequence won't split into 2R chunks — contiguous ring still
            # computes the exact result, just with imbalanced causal work.
            return call("contiguous", q, k, v, segment_ids)
        perm = zigzag_indices(s, ring)
        inv = inverse_permutation(perm)
        qz, kz, vz = (jnp.take(x, perm, axis=1) for x in (q, k, v))
        segz = (
            None if segment_ids is None
            else jnp.take(segment_ids, perm, axis=1)
        )
        out = call("zigzag", qz, kz, vz, segz)
        return jnp.take(out, inv, axis=1)

    return wrapper


def reference_attention(q, k, v, *, causal: bool = True, scale=None,
                        window: Optional[int] = None,
                        segment_ids: Optional[jax.Array] = None):
    """Unsharded reference for tests (and the dense dispatch path): plain
    softmax attention with the same band/segment mask model as the flash
    kernel. `segment_ids`: [B, S] ids, attention only within equal ids."""
    if window is not None and not causal:
        # Same contract as the flash kernels: without causality the band
        # would still admit every FUTURE key, which is not a "window" in
        # any useful sense — better the same ValueError on every backend
        # than a CPU-only silent semantic.
        raise ValueError("window (sliding-window) requires causal=True")
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s_q, s_k = scores.shape[-2:]
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))[None, None]
    if window is not None:
        wm = (
            jnp.arange(s_q)[:, None] - jnp.arange(s_k)[None, :] < window
        )[None, None]
        mask = wm if mask is None else mask & wm
    if segment_ids is not None:
        sm = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None]
        mask = sm if mask is None else mask & sm
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        # Rows with no live key (a segment matching nothing) softmax
        # all-(-inf) to NaN; they are defined as zero output (the kernel's
        # l == 0 guard). Scrub ONLY those rows — a blanket NaN scrub would
        # swallow genuine numerical divergence on this production path.
        empty = jnp.logical_not(jnp.any(mask, axis=-1, keepdims=True))
        p = jnp.where(empty, 0.0, p)
    else:
        p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)
