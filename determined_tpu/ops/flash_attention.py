"""Flash attention: fused blockwise attention for the MXU.

Net-new vs. the reference (its attention lived inside torch/DeepSpeed
kernels). Two implementations behind one differentiable entry point:

- ``_flash_fwd_pallas``: a Pallas TPU kernel — the K/V loop is the innermost
  grid dimension, with running (m, l, acc) softmax state in VMEM scratch that
  persists across that dimension (the standard TPU flash pattern from the
  Pallas guide: grid-as-reduction + @pl.when epilogue). bfloat16-friendly:
  matmuls hit the MXU with fp32 accumulation via preferred_element_type.
- ``_blockwise_*_ref``: a lax.scan blockwise path with identical math, used
  for CPU tests/interpret mode and as the autodiff backward (recompute
  per-block scores from the saved LSE — O(S·block) memory, never O(S²)).

Masking is a single band+segment model shared by every kernel:

- ``causal``: row r attends cols ≤ r;
- ``window=W``: row r additionally attends only cols > r − W (sliding
  window; requires causal);
- ``kv_offset``: q positions are globally offset by +kv_offset relative to
  k positions — this is what lets ring attention express a cross-device hop
  ("my queries sit s·L tokens after this kv chunk") as a plain kernel call,
  and what a kv-cache decode layout needs;
- ``segment_ids``: attention only within equal ids (packed sequences).

Block-sparse causal execution (the long-context win): blocks that the
band proves fully dead are skipped at BOTH levels —

- compute: the @pl.when dispatch in `_mask_dispatch` never runs the MXU
  work for a dead (qi, ki) block;
- DMA: the K/V (resp. Q-side, in the dk/dv grids) BlockSpec index_maps
  remap dead iterations onto a block that is already resident — Pallas
  elides the HBM copy when consecutive grid steps map the same block (the
  jax-ml TPU flash-attention technique). Dead iterations past a row's live
  range map to the NEXT row's first live block, so its DMA overlaps the
  dead tail instead of stalling the row start.

At 32k causal that removes ~half the grid's HBM traffic; with a sliding
window it removes all blocks outside the band. `block_skip_stats` mirrors
the predicate for bench reporting.

The custom VJP follows the flash-attention backward equations:
  p  = exp(s - lse);  dv = pᵀ·do;  dp = do·vᵀ
  ds = p ∘ (dp - rowsum(do ∘ o));  dq = ds·k;  dk = dsᵀ·q
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class _LazyPallas:
    """Deferred `jax.experimental.pallas` import: every `pl.` reference in
    this module is inside a function body, and importing pallas eagerly
    costs ~1 s per process (it drags the mosaic-gpu interpret machinery
    in) — pure waste for CPU-only trial processes that never call a
    kernel. First attribute access swaps the real module into place."""

    def __getattr__(self, name):
        from jax.experimental import pallas

        globals()["pl"] = pallas
        return getattr(pallas, name)


pl = _LazyPallas()

NEG_INF = float(-1e30)  # finite mask value; true -inf breaks m-subtraction

# No `pallas_call` below passes `name=`, and callers open no
# `jax.named_scope` around them: XLA names a Mosaic kernel's operation after
# the innermost component of jax's name stack, and the names that gives with
# none (`jvp__`, `transpose_jvp___`, `shard_map`) are what
# `benchmark/kernels/flash_*.json` find these kernels by in a trace. Forward
# and backward are told apart there by the `transpose(...)` around the
# backward's name stack. Name them once those files can follow.


def fit_block(seq: int, want: int) -> int:
    """Largest block size ≤ `want` dividing `seq` (the kernel requires
    block | seq). Prefers lane-friendly multiples of 128 when one divides;
    falls back to the largest plain divisor (correct at any size, just less
    MXU-efficient). Callers with tuned block sizes use this so a sequence
    that isn't a multiple of the tuned block degrades instead of raising."""
    want = min(want, seq)
    for b in range(want - want % 128, 0, -128):
        if seq % b == 0:
            return b
    b = want
    while seq % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# Masking model: band (causal/window/kv_offset) + segments
# ---------------------------------------------------------------------------
def _band_mask(qi, ki, bq: int, bk: int, *, causal: bool,
               window: Optional[int], kv_offset: int) -> jax.Array:
    """Elementwise [bq, bk] mask for one block: q position (global) is
    qi·bq + r + kv_offset, k position is ki·bk + c."""
    rows = qi * bq + kv_offset + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = None
    if causal:
        mask = rows >= cols
    if window is not None:
        wm = rows - cols < window
        mask = wm if mask is None else mask & wm
    assert mask is not None
    return mask


def _score_mask(qi, ki, *, block_q: int, block_k: int, causal: bool,
                window: Optional[int], kv_offset: int, band_masked: bool,
                qseg, kseg):
    """Combined [bq, bk] bool mask, or None when nothing masks this block.
    `qseg`/`kseg` are the (block_q, 1) / (1, block_k) fp32 segment-id
    values (or None) — fp32 equality is exact for ids < 2^24 and keeps the
    arrays out of the custom_vjp's integer-cotangent corner."""
    mask = None
    if band_masked:
        mask = _band_mask(
            qi, ki, block_q, block_k,
            causal=causal, window=window, kv_offset=kv_offset,
        )
    if qseg is not None:
        sm = qseg == kseg  # broadcasts to [bq, bk]
        mask = sm if mask is None else mask & sm
    return mask


def _mask_dispatch(qi, ki, *, block_q, block_k, causal, window, kv_offset,
                   compute):
    """Run `compute(band_masked)` for one (qi, ki) block in the right band
    regime — shared by all the blocked kernels so the boundary logic lives
    once:

    - block fully outside the band (above the diagonal, or entirely past
      the sliding window): contributes nothing, skip all work;
    - block straddling a band edge: compute with the element mask;
    - block fully inside: compute without the iota/where VPU work
      (segment masking, when active, is applied inside `compute` either
      way — segment boundaries aren't derivable from block indices).
    """
    if not causal and window is None:
        compute(band_masked=False)
        return
    first_q = qi * block_q + kv_offset
    last_q = first_q + block_q - 1
    first_k = ki * block_k
    last_k = first_k + block_k - 1
    live = None
    inside = None

    def _and(a, b):
        return b if a is None else a & b

    if causal:
        live = _and(live, first_k <= last_q)
        inside = _and(inside, last_k <= first_q)
    if window is not None:
        live = _and(live, last_k >= first_q - (window - 1))
        inside = _and(inside, first_k >= last_q - (window - 1))
    # `inside` ⊆ `live` componentwise, so edge, inside and the skipped
    # rest cover the grid.
    on_edge = live & jnp.logical_not(inside)

    @pl.when(on_edge)
    def _():
        compute(band_masked=True)

    @pl.when(inside)
    def _():
        compute(band_masked=False)


# ---------------------------------------------------------------------------
# Dead-block DMA elision: BlockSpec index_map remapping
# ---------------------------------------------------------------------------
def _remap_k_index(i, j, *, block_q, block_k, causal, window, kv_offset, nk):
    """K-side block index for grid step (qi=i, ki=j) in a k-innermost grid.

    Live ki range for row i is [kmin(i), kmax(i)]; dead iterations below
    map to kmin(i) (prefetching the row's first live block) and dead
    iterations above map to kmin(i+1) (prefetching the NEXT row's first
    live block — for plain causal that is block 0, the jax-ml trick).
    Pallas elides the copy whenever consecutive steps map the same block,
    so dead iterations cost no HBM traffic."""
    if not causal and window is None:
        return j
    last_q = i * block_q + block_q - 1 + kv_offset
    kmax = jnp.minimum(last_q // block_k, nk - 1) if causal else nk - 1
    if window is not None:
        first_q = i * block_q + kv_offset
        kmin = jnp.maximum(first_q - (window - 1), 0) // block_k
        first_q2 = first_q + block_q
        kmin_next = jnp.maximum(first_q2 - (window - 1), 0) // block_k
    else:
        kmin = 0
        kmin_next = 0
    j_eff = jnp.where(j > kmax, kmin_next, jnp.clip(j, kmin, kmax))
    return jnp.clip(j_eff, 0, nk - 1)


def _remap_q_index(j, i, *, block_q, block_k, causal, window, kv_offset, nq):
    """Q-side block index for grid step (ki=j, qi=i) in a q-innermost grid
    (the dk/dv kernels). Mirror of `_remap_k_index`: live qi range for
    column j is [imin(j), imax(j)]."""
    if not causal and window is None:
        return i
    first_k = j * block_k
    # smallest i with i·bq + bq − 1 + off ≥ first_k, i.e.
    # ceil((first_k − off − bq + 1)/bq) = floor((first_k − off)/bq);
    # jnp's // floors (lax.div would truncate negatives toward zero).
    imin = jnp.maximum((first_k - kv_offset) // block_q, 0)
    if window is not None:
        last_k = first_k + block_k - 1
        imax = jnp.minimum(
            (last_k + window - 1 - kv_offset) // block_q, nq - 1
        )
        imin_next = jnp.maximum(
            (first_k + block_k - kv_offset) // block_q, 0)
    else:
        imax = nq - 1
        imin_next = imin  # no dead-above iterations without a window
    i_eff = jnp.where(i > imax, imin_next, jnp.clip(i, imin, imax))
    return jnp.clip(i_eff, 0, nq - 1)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, window, kv_offset,
                has_segments, block_q, block_k, num_k_blocks):
    if has_segments:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(band_masked):
        # MXU dots take the native (bf16) inputs and accumulate in fp32 via
        # preferred_element_type — casting inputs to fp32 first would run
        # the MXU at a fraction of its bf16 rate.
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk] fp32
        mask = _score_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, kv_offset=kv_offset, band_masked=band_masked,
            qseg=qseg_ref[0].reshape(block_q, 1) if has_segments else None,
            kseg=kseg_ref[0].reshape(1, block_k) if has_segments else None,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        # m/l live in lane-padded (block_q, 128) scratch; column 0 is real.
        m_prev = m_scr[:, 0:1]  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0:1] = l_scr[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0:1] = m_new

    _mask_dispatch(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, kv_offset=kv_offset, compute=_compute,
    )

    @pl.when(ki == num_k_blocks - 1)
    def _epilogue():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = (m_scr[:, 0:1] + jnp.log(l_safe)).astype(lse_ref.dtype)  # [bq, 1]
        lse_ref[0] = lse.reshape(1, block_q)


def _seg3(segs, s_q, s_k):
    """([BH, Sq], [BH, Sk]) fp32 segment ids → the [BH, 1, S] layout the
    kernels' (1, 1, block) BlockSpecs want (same TPU-tiling trick as lse)."""
    qseg, kseg = segs
    bh = qseg.shape[0]
    return qseg.reshape(bh, 1, s_q), kseg.reshape(bh, 1, s_k)


def _flash_fwd_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *, scale, causal, block_q,
    block_k, interpret, window=None, kv_offset=0, segs=None,
) -> Tuple[jax.Array, jax.Array]:
    """The blocked forward. q/k/v: [BH, S, D] (+ optional segs ([BH, Sq],
    [BH, Sk]) fp32) → (o [BH, S, D], lse [BH, S])."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    nq = pl.cdiv(s_q, block_q)
    nk = pl.cdiv(s_k, block_k)
    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        window=window,
        kv_offset=kv_offset,
        has_segments=segs is not None,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
    )
    from jax.experimental.pallas import tpu as pltpu

    kmap = functools.partial(
        _remap_k_index, block_q=block_q, block_k=block_k, causal=causal,
        window=window, kv_offset=kv_offset, nk=nk,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kmap(i, j), 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kmap(i, j), 0)),
    ]
    inputs = [q, k, v]
    if segs is not None:
        qseg3, kseg3 = _seg3(segs, s_q, s_k)
        in_specs.append(pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)))
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, kmap(i, j)))
        )
        inputs.extend([qseg3, kseg3])
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # lse as [BH, 1, S]: block (1, 1, block_q) satisfies TPU tiling
            # (second-to-last block dim == full array dim; last divisible by 128).
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return o, lse.reshape(bh, s_q)


# ---------------------------------------------------------------------------
# Monolithic (single-block) kernels: when one block spans the whole
# sequence — the GPT-2-class regime, S ≤ ~1k — the blocked kernels' online
# softmax machinery (m/l scratch read-modify-writes, correction multiplies,
# @pl.when dispatch) is pure overhead. These do plain softmax, and the
# backward produces dq/dk/dv in ONE pass: 5 MXU dots + 1 exp a score, as
# the blocked one does where a head's dq fits VMEM (the split pair's 7 + 2).
#
# Under the causal mask they walk the score matrix in static row chunks
# (a Python loop, unrolled at trace time) and give chunk r0..r1 only the
# keys 0..r1 it can see: every row's whole live range is in its chunk, so
# there is no running max or sum. Scores are held transposed, [keys, rows]:
# the row statistics are then lane vectors like the lse block, p^T feeds
# dv and ds^T feeds dk without a transpose of a score-sized array, and
# the products that stream the long key axis keep one small weight tile.
# Not causal, or s_q within one chunk, is the one-chunk case of the same
# body. Measured on a v5e at S = 1024, D = 64, in GPT-2 small's train step
# (PERF.md section 6, PR 26), a head: forward 4.15 -> 2.38 us, backward
# 7.00 -> 4.45 us, against the whole-square kernels these replace. Chunks
# alone, both passes timed outside a step: the forward wants 512 rows
# (3.06 us; 256: 4.15, 384: 3.71, 768: 3.55 — a narrower score block
# leaves MXUs idle, a wider one computes more of the dead triangle), the
# backward 256 (6.27 us with its row sums; 128: 6.43, 512 and one chunk
# more; the order of its five products moves it by as much). D = 128 reads
# the same way (forward 5.09 -> 3.72, backward 8.71 -> 6.06).
#
# Layout. The operands are [B, H*D, S]: heads side by side on the rows, a
# head's D rows one block, the sequence on the lanes (any head count tiles:
# 25 x 64 as 12 x 64). That is the layout XLA's TPU compiler gives what a
# `bsd,dthk->bsthk` projection writes and a `bshk,hkd->bsd` one reads in
# GPT's train step (sequence minor; seen in the compiled programs of both
# benchmark cells), so `flash_attention_lse`'s transposes to and from
# [B, S, H, D] are bitcasts there, where folding to a head a row,
# [BH, S, D], cost a copy an operand and a result: eight a layer, 6.8 % of
# GPT-2 small's step. `flash_attention_qkv` goes one further and hands the
# kernels the fused projection itself, [B, 3, H*D, S], a block of which
# is a head's q^T, k^T and v^T (and in the backward the three gradients):
# then the three slices and the gradient's assembly are no passes over
# memory either. With the sequence on the lanes q^T, do^T, o^T, dq^T,
# dk^T and dv^T are what the products above take and give, K and V are
# transposed once a call, and a [d, keys] fp32 accumulator has no padded
# lanes. A head alone, S = 1024, D = 64 (PERF.md section 6, PR 29):
# forward 2.93 -> 2.64 us, backward with its row sums 5.96 -> 4.46.
# Every other call (segments, a window, `kv_offset`, blocks smaller than
# the sequence, a head width that is not whole sublane tiles) folds to
# [BH, S, D] and takes the blocked kernels below, as before.
# ---------------------------------------------------------------------------
#: Largest s_q*s_k (score-matrix elements) the monolithic path may buy:
#: ~3 fp32 [s_q, s_k] temporaries must fit VMEM alongside the q/k/v/do
#: blocks when a call is one chunk. 2^21 elements = 8 MB per temporary.
_MONO_MAX_SCORES = 2 ** 21

#: Query rows a causal chunk takes, forward and backward (timings above):
#: two module constants chosen on the chip, not options.
_MONO_CHUNK_FWD = 512
_MONO_CHUNK_BWD = 256


def _mono_ok(s_q, s_k, block_q, block_k, *, window=None, has_segments=False,
             kv_offset=0) -> bool:
    """Mono engages only for the plain (no window/segments/offset) shapes
    it was written for; windowed/segmented/offset calls take the blocked
    kernels, whose band dispatch handles them. The mono-vs-blocked choice
    itself is empirical: the autotuner includes the (s_q, s_k) mono
    candidate in its probe set when it fits."""
    return (
        block_q == s_q and block_k == s_k
        and s_q * s_k <= _MONO_MAX_SCORES
        and window is None and not has_segments and kv_offset == 0
    )


def _mono_tiles(head_dim: int, *dtypes) -> bool:
    """A head is `head_dim` rows of the monolithic kernels' operands
    ([B, H*D, S]): a block of whole sublane tiles (8 rows of 32 bits) of
    the one dtype q, k and v share."""
    return len(set(dtypes)) == 1 and not head_dim % (
        32 // jnp.dtype(dtypes[0]).itemsize)


def _mono_chunks(s_q: int, s_k: int, causal: bool, chunk: int):
    """Static (r0, r1, k1) row chunks covering the live scores: query rows
    r0..r1 against keys 0..k1, all a causal row below r1 can see. Not
    causal: one chunk, every key."""
    if not causal:
        return [(0, s_q, s_k)]
    return [(r0, min(r0 + chunk, s_q), min(r0 + chunk, s_q, s_k))
            for r0 in range(0, s_q, chunk)]


def _dot(a, b, contract_a: int, contract_b: int):
    """2-D MXU product in the operands' own dtype, fp32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _mono_scores(k, qt, r0: int, *, scale, causal):
    """[keys, rows] fp32 scores of query rows r0.. (`qt` [d, rows]) against
    keys 0.. (`k` [keys, d]); what the causal mask hides is NEG_INF, so
    exp() of it is exactly 0."""
    st = _dot(k, qt, 1, 0) * scale
    if causal:
        keys = lax.broadcasted_iota(jnp.int32, st.shape, 0)
        rows = r0 + lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st = jnp.where(rows >= keys, st, NEG_INF)
    return st


def _fwd_kernel_mono(*refs, scale, causal, fused):
    """One head: q^T [d, s_q], k^T and v^T [d, s_k] in, o^T and lse out."""
    (qt_ref, kt_ref, vt_ref), (ot_ref, lse_ref) = _qkv_refs(refs, fused)
    s_q, s_k = qt_ref.shape[2], kt_ref.shape[2]
    k = kt_ref[0].T  # [s_k, d]
    for r0, r1, k1 in _mono_chunks(s_q, s_k, causal, _MONO_CHUNK_FWD):
        st = _mono_scores(k[:k1], qt_ref[0, :, r0:r1], r0, scale=scale,
                          causal=causal)
        # Mono never sees a row without a live key (no offset, no
        # segments): m is a real score and l >= 1.
        m = jnp.max(st, axis=0, keepdims=True)  # [1, rows]
        pt = jnp.exp(st - m)
        l = jnp.sum(pt, axis=0, keepdims=True)
        acc = _dot(vt_ref[0, :, :k1], pt.astype(vt_ref.dtype), 1, 0)
        ot_ref[0, :, r0:r1] = (acc / l).astype(ot_ref.dtype)  # [d, rows]
        lse_ref[0, :, r0:r1] = m + jnp.log(l)


def _bwd_kernel_mono(*refs, scale, causal, fused):
    """Fused single-pass backward of one head, operands as the forward's:
    s and p are computed ONCE a chunk and feed all three gradients (the
    blocked split recomputes them per pass). dq^T is written once a
    chunk; dk^T/dv^T sum over the chunks in the fp32 `scratch` pair and
    are cast once (one chunk: written straight out, and the call
    allocates no scratch)."""
    (qt_ref, kt_ref, vt_ref), refs = _qkv_refs(refs, fused)
    dot_ref, lse_ref, delta_ref, dlse_ref, *refs = refs
    (dqt_ref, dkt_ref, dvt_ref), scratch = _qkv_refs(refs, fused)
    s_q, s_k = qt_ref.shape[2], kt_ref.shape[2]
    k, v = kt_ref[0].T, vt_ref[0].T  # [s_k, d]
    seen = 0  # keys whose dk/dv columns hold a partial sum already
    for r0, r1, k1 in _mono_chunks(s_q, s_k, causal, _MONO_CHUNK_BWD):
        qt = qt_ref[0, :, r0:r1]    # [d, rows] bf16
        dot = dot_ref[0, :, r0:r1]
        # Both score-shaped products first: the MXU runs the second while
        # the VPU is still on the first's softmax (measured: of four
        # orders this one was fastest at both head widths).
        dpt = _dot(v[:k1], dot, 1, 0)                     # [keys, rows]
        st = _mono_scores(k[:k1], qt, r0, scale=scale, causal=causal)
        pt = jnp.exp(st - lse_ref[0, :, r0:r1])           # fp32
        # dL/ds = p∘(dp − delta + dlse); the two row terms meet first.
        shift = delta_ref[0, :, r0:r1] - dlse_ref[0, :, r0:r1]
        dst = (pt * (dpt - shift) * scale).astype(qt.dtype)
        parts = (_dot(dot, pt.astype(dot.dtype), 1, 1), _dot(qt, dst, 1, 1))
        dqt_ref[0, :, r0:r1] = _dot(kt_ref[0, :, :k1], dst, 1, 0).astype(
            dqt_ref.dtype)
        for ref, acc, part in zip((dvt_ref, dkt_ref), scratch or (None,) * 2,
                                  parts):                  # [d, keys] fp32
            if acc is None:
                ref[0, :, :k1] = part.astype(ref.dtype)
                continue
            if seen:
                acc[:, :seen] += part[:, :seen]
            if k1 > seen:
                acc[:, seen:k1] = part[:, seen:]
        seen = k1
    for ref, acc in zip((dvt_ref, dkt_ref), scratch):
        ref[0, :, :seen] = acc[:, :seen].astype(ref.dtype)
    if seen < s_k:  # causal with more keys than queries: no row sees them
        dkt_ref[0, :, seen:] = jnp.zeros_like(dkt_ref[0, :, seen:])
        dvt_ref[0, :, seen:] = jnp.zeros_like(dvt_ref[0, :, seen:])


def _bwd_fused_blocked_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, dlse_ref, *rest, scale, causal,
                              window, kv_offset, has_segments, block_q,
                              block_k, num_q_blocks, num_k_blocks):
    """Fused blocked backward: ONE pass over (j, i) blocks computes s and
    p once and feeds all three gradients — the split pair below recomputes
    them (7 matmuls + 2 exps per block pair vs 5 + 1 here) and reads every
    q/k/v/do block a second time. The grid is k-major, so dk/dv accumulate
    in VMEM scratch over the inner q dimension. dq accumulates over the
    OUTER dimension, so the whole head's dq is a VMEM scratch too,
    [nq, block_q, d] fp32: block i is zeroed at its first visit (j = 0;
    the chip hands scratch out as the last kernel left it), summed over
    j = 0, 1, … in the dq kernel's order, and cast and written out on the
    last key block's pass, where it is final."""
    if has_segments:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    ji = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(ji == 0)
    def _init_q():
        dq_scr[qi] = jnp.zeros(dq_scr.shape[1:], dq_scr.dtype)

    def _compute(band_masked):
        q = q_ref[0]    # [bq, d] bf16
        k = k_ref[0]    # [bk, d]
        v = v_ref[0]
        do = do_ref[0]  # [bq, d]
        lse = lse_ref[0].reshape(block_q, 1)
        delta = delta_ref[0].reshape(block_q, 1)
        dlse = dlse_ref[0].reshape(block_q, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _score_mask(
            qi, ji, block_q=block_q, block_k=block_k, causal=causal,
            window=window, kv_offset=kv_offset, band_masked=band_masked,
            qseg=qseg_ref[0].reshape(block_q, 1) if has_segments else None,
            kseg=kseg_ref[0].reshape(1, block_k) if has_segments else None,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                    # [bq, bk] fp32
        if mask is not None:
            # Rows with NO live keys carry lse ≈ NEG_INF; exp(s − lse)
            # would resurrect masked entries as 1 there.
            p = jnp.where(mask, p, 0.0)
        pt = p.astype(do.dtype)
        dv_scr[:] += jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta + dlse) * scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_scr[qi] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _mask_dispatch(
        qi, ji, block_q=block_q, block_k=block_k, causal=causal,
        window=window, kv_offset=kv_offset, compute=_compute,
    )

    @pl.when(ji == num_k_blocks - 1)
    def _dq_out():
        dq_ref[0] = dq_scr[qi].astype(dq_ref.dtype)

    @pl.when(qi == num_q_blocks - 1)
    def _epilogue():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


#: VMEM the fused blocked backward may give its dq accumulator, a head's
#: [s_q, d] in fp32: 16 MiB holds a 16k sequence at d 256 (the 8k cells'
#: heads of 256 take 8 MiB) or 64k at d 64. With the blocks the pipeline
#: double-buffers and the fp32 score temporaries
#: (`_fused_bwd_vmem_bytes`) that is under 32 MiB at block 512, a
#: quarter of a v5e core's 128 MiB. Past it the split pair runs, whose
#: accumulators are a block each.
_DQ_VMEM_BUDGET = 16 << 20


def _fused_bwd_vmem_bytes(s_q, d, block_q, block_k, itemsize):
    """Scoped VMEM the fused blocked backward asks the compiler for: the
    dq, dk and dv accumulators; two buffers of every block it reads (q,
    do, k, v, and the row vectors, padded to 8 sublanes) and writes (dq,
    dk, dv); and six [block_q, block_k] fp32 temporaries (s, p, dp, ds,
    the mask and a cast)."""
    scratch = (s_q + 2 * block_k) * d * 4
    blocks = (2 * block_q + 2 * block_k) * d * itemsize   # q, do, k, v
    blocks += (block_q + 2 * block_k) * d * itemsize      # dq, dk, dv
    blocks += 5 * 8 * max(block_q, block_k) * 4           # lse, delta, …
    return scratch + 2 * blocks + 6 * block_q * block_k * 4


# ---------------------------------------------------------------------------
# Pallas backward kernels (TPU): dq pass + dk/dv pass.
#
# The split flash backward, for a head whose dq passes `_DQ_VMEM_BUDGET`:
# recomputing p costs one extra QK^T matmul per pass but keeps every
# accumulator a block of VMEM scratch — dq accumulates over the k-block
# grid dimension, dk/dv over the q-block dimension. All MXU dots take bf16
# inputs with fp32 accumulation.
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
                   *rest, scale, causal, window, kv_offset, has_segments,
                   block_q, block_k, num_k_blocks):
    if has_segments:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
    dq_ref, dq_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(band_masked):
        q = q_ref[0]    # [bq, d] bf16
        k = k_ref[0]    # [bk, d]
        v = v_ref[0]
        do = do_ref[0]  # [bq, d]
        lse = lse_ref[0].reshape(block_q, 1)    # [bq, 1] fp32
        delta = delta_ref[0].reshape(block_q, 1)
        dlse = dlse_ref[0].reshape(block_q, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _score_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, kv_offset=kv_offset, band_masked=band_masked,
            qseg=qseg_ref[0].reshape(block_q, 1) if has_segments else None,
            kseg=kseg_ref[0].reshape(1, block_k) if has_segments else None,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk] fp32
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # all-masked rows: lse ≈ NEG_INF
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dL/ds = p∘(dp − delta + dlse): the dlse term is the cotangent of
        # the returned log-sum-exp (dlse/ds_k = p_k), which ring attention
        # feeds back through its partial-softmax merge.
        ds = (p * (dp - delta + dlse) * scale).astype(q.dtype)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _mask_dispatch(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, kv_offset=kv_offset, compute=_compute,
    )

    @pl.when(ki == num_k_blocks - 1)
    def _epilogue():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref,
                    *rest, scale, causal, window, kv_offset, has_segments,
                    block_q, block_k, num_q_blocks):
    if has_segments:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
    dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(band_masked):
        q = q_ref[0]    # [bq, d]
        k = k_ref[0]    # [bk, d]
        v = v_ref[0]
        do = do_ref[0]  # [bq, d]
        lse = lse_ref[0].reshape(block_q, 1)
        delta = delta_ref[0].reshape(block_q, 1)
        dlse = dlse_ref[0].reshape(block_q, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _score_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, kv_offset=kv_offset, band_masked=band_masked,
            qseg=qseg_ref[0].reshape(block_q, 1) if has_segments else None,
            kseg=kseg_ref[0].reshape(1, block_k) if has_segments else None,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                    # [bq, bk] fp32
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # all-masked rows: lse ≈ NEG_INF
        pt = p.astype(do.dtype)
        dv_scr[:] += jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),   # pᵀ·do → [bk, d]
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta + dlse) * scale).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),    # dsᵀ·q → [bk, d]
            preferred_element_type=jnp.float32,
        )

    _mask_dispatch(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, kv_offset=kv_offset, compute=_compute,
    )

    @pl.when(qi == num_q_blocks - 1)
    def _epilogue():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _mono_specs(h, d, s_q, s_k, fused):
    """Block specs of the monolithic kernels, one head a step of the grid
    (B, h). `head(s)`: head j's [d, s] rows of a [B, h*d, s] operand.
    `qkv`: the specs of q^T, k^T, v^T or of their gradients, three such,
    or with `fused` the one of a [B, 3, h*d, s] projection, a block of
    which holds head j's rows of all three (`_qkv_refs`). `vec`: the
    head's row of the per-row statistics [B*h, 1, s_q] (lse, delta, dlse:
    lane vectors, as the blocked kernels keep them)."""
    def head(s):
        return pl.BlockSpec((1, d, s), lambda b, j: (b, j, 0))

    qkv = [head(s_q), head(s_k), head(s_k)]
    if fused:
        qkv = [pl.BlockSpec((1, 3, d, s_q), lambda b, j: (b, 0, j, 0))]
    vec = pl.BlockSpec((1, 1, s_q), lambda b, j: (b * h + j, 0, 0))
    return qkv, head, vec


def _qkv_refs(refs, fused):
    """(the q^T, k^T, v^T blocks [1, d, s] at the head of `refs`, the
    rest): three refs, or the three parts of one fused block."""
    if fused:
        return [refs[0].at[:, part] for part in range(3)], refs[1:]
    return refs[:3], refs[3:]


@functools.lru_cache(maxsize=None)
def _mono_fwd_fn(b, h, d, s_q, s_k, fused, dtype, scale, causal, interpret):
    """The monolithic forward for one shape, built once a process: every
    layer of a model then calls the same jitted callable, so jax traces the
    kernel body and lowers it to Mosaic once a program and not once a
    layer. On the chip's host that is seconds of set-up, and the unrolled
    chunks would have added to them (PERF.md section 6, PR 26)."""
    qkv, head, vec = _mono_specs(h, d, s_q, s_k, fused)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_mono, scale=scale, causal=causal,
                          fused=fused),
        grid=(b, h),
        in_specs=qkv,
        out_specs=[head(s_q), vec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h * d, s_q), dtype),
            jax.ShapeDtypeStruct((b * h, 1, s_q), jnp.float32),
        ],
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _mono_bwd_fn(b, h, d, s_q, s_k, fused, dtype, scale, causal, interpret):
    """The monolithic backward for one shape, built once a process (as
    `_mono_fwd_fn`)."""
    from jax.experimental.pallas import tpu as pltpu

    qkv, head, vec = _mono_specs(h, d, s_q, s_k, fused)
    n_chunks = len(_mono_chunks(s_q, s_k, causal, _MONO_CHUNK_BWD))
    shapes = [(b, 3, h * d, s_q)] if fused else [
        (b, h * d, s) for s in (s_q, s_k, s_k)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel_mono, scale=scale, causal=causal,
                          fused=fused),
        grid=(b, h),
        in_specs=qkv + [head(s_q), vec, vec, vec],
        out_specs=qkv,
        out_shape=[jax.ShapeDtypeStruct(shape, dtype) for shape in shapes],
        # dk and dv, summed over the chunks in fp32
        scratch_shapes=[pltpu.VMEM((d, s_k), jnp.float32)]
        * (2 if n_chunks > 1 else 0),
        interpret=interpret,
    )


def _flash_bwd_pallas(q, k, v, o, lse, do, *, scale, causal, block_q, block_k,
                      interpret=False, dlse=None, window=None, kv_offset=0,
                      segs=None):
    """The blocked backward. q/k/v/o/do: [BH, S, D], lse (+optional dlse):
    [BH, S] fp32 → (dq, dk, dv)."""
    from jax.experimental.pallas import tpu as pltpu

    bh, s_q, d = q.shape
    s_k = k.shape[1]
    nq = pl.cdiv(s_q, block_q)
    nk = pl.cdiv(s_k, block_k)
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # [BH, Sq]
    if dlse is None:
        dlse = jnp.zeros_like(lse)
    lse3 = lse.reshape(bh, 1, s_q)
    delta3 = delta.reshape(bh, 1, s_q)
    dlse3 = dlse.astype(jnp.float32).reshape(bh, 1, s_q)
    has_segments = segs is not None

    qmap = functools.partial(
        _remap_q_index, block_q=block_q, block_k=block_k, causal=causal,
        window=window, kv_offset=kv_offset, nq=nq,
    )
    kmap = functools.partial(
        _remap_k_index, block_q=block_q, block_k=block_k, causal=causal,
        window=window, kv_offset=kv_offset, nk=nk,
    )
    inputs = [q, k, v, do, lse3, delta3, dlse3]
    if segs is not None:
        inputs.extend(_seg3(segs, s_q, s_k))

    # q-innermost grid (the fused kernel and the dk/dv pass): q-side blocks
    # remap dead iterations for DMA elision; the k/v blocks are fixed per
    # outer step.
    col_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, qmap(j, i), 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, qmap(j, i), 0)),   # do
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, qmap(j, i))),   # lse
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, qmap(j, i))),   # delta
        pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, qmap(j, i))),   # dlse
    ]
    if has_segments:
        col_specs.append(
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, qmap(j, i)))
        )
        col_specs.append(pl.BlockSpec((1, 1, block_k), lambda b, j, i: (b, 0, j)))
    dkv_specs = [
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
    ]
    dkv_shapes = [
        jax.ShapeDtypeStruct((bh, s_k, d), k.dtype),
        jax.ShapeDtypeStruct((bh, s_k, d), v.dtype),
    ]
    dkv_scratch = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, d), jnp.float32),
    ]

    if s_q * d * 4 <= _DQ_VMEM_BUDGET:
        # dq block i leaves on the last key block's pass (j = nk − 1); until
        # then its index stays where that pass starts, so nothing is
        # written back before it is final.
        dq_spec = pl.BlockSpec(
            (1, block_q, d),
            lambda b, j, i: (b, jnp.where(j == nk - 1, i, 0), 0),
        )
        vmem = _fused_bwd_vmem_bytes(s_q, d, block_q, block_k,
                                     max(x.dtype.itemsize for x in (q, k, v)))
        return tuple(pl.pallas_call(
            functools.partial(
                _bwd_fused_blocked_kernel, scale=scale, causal=causal,
                window=window, kv_offset=kv_offset,
                has_segments=has_segments, block_q=block_q, block_k=block_k,
                num_q_blocks=nq, num_k_blocks=nk,
            ),
            grid=(bh, nk, nq),
            in_specs=col_specs,
            out_specs=[dq_spec] + dkv_specs,
            out_shape=[jax.ShapeDtypeStruct((bh, s_q, d), q.dtype)]
            + dkv_shapes,
            scratch_shapes=[pltpu.VMEM((nq, block_q, d), jnp.float32)]
            + dkv_scratch,
            # a whole head's dq can pass the default scoped limit (16 MiB
            # on a v5e); never ask for less than it
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=max(vmem, 16 << 20)),
            interpret=interpret,
        )(*inputs))

    row_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kmap(i, j), 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kmap(i, j), 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # do
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),   # lse
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),   # delta
        pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),   # dlse
    ]
    if has_segments:
        row_specs.append(pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)))
        row_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, kmap(i, j)))
        )
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            window=window, kv_offset=kv_offset, has_segments=has_segments,
            block_q=block_q, block_k=block_k, num_k_blocks=nk,
        ),
        grid=(bh, nq, nk),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*inputs)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            window=window, kv_offset=kv_offset, has_segments=has_segments,
            block_q=block_q, block_k=block_k, num_q_blocks=nq,
        ),
        grid=(bh, nk, nq),
        in_specs=col_specs,
        out_specs=dkv_specs,
        out_shape=dkv_shapes,
        scratch_shapes=dkv_scratch,
        interpret=interpret,
    )(*inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Blockwise scan reference (CPU path + backward recompute)
# ---------------------------------------------------------------------------
def _ref_block_mask(rows, cols, *, causal, window, kv_offset, qseg, kseg_j):
    """[.., s_q, bk] bool mask (or None) for the scan reference. `rows` is
    [s_q] LOCAL q indices, `cols` [bk] global k indices; `qseg` [BH, s_q]
    and `kseg_j` [BH, bk] fp32 ids."""
    grows = rows + kv_offset
    mask = None
    if causal:
        mask = grows[:, None] >= cols[None, :]
    if window is not None:
        wm = grows[:, None] - cols[None, :] < window
        mask = wm if mask is None else mask & wm
    if mask is not None:
        mask = mask[None]  # broadcast over BH
    if qseg is not None:
        sm = qseg[:, :, None] == kseg_j[:, None, :]
        mask = sm if mask is None else mask & sm
    return mask


def _blockwise_fwd_ref(q, k, v, *, scale, causal, block_k, window=None,
                       kv_offset=0, segs=None):
    """Same math as the kernel, expressed as lax.scan over K/V blocks."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    nk = s_k // block_k
    kb = k.reshape(bh, nk, block_k, d).transpose(1, 0, 2, 3)
    vb = v.reshape(bh, nk, block_k, d).transpose(1, 0, 2, 3)
    rows = jnp.arange(s_q)
    qseg = None
    ksegb = jnp.zeros((nk, bh, block_k), jnp.float32)  # placeholder xs slot
    if segs is not None:
        qseg, kseg = segs
        ksegb = kseg.reshape(bh, nk, block_k).transpose(1, 0, 2)
    masked = causal or window is not None or segs is not None

    def step(carry, blk):
        m, l, acc = carry
        k_j, v_j, kseg_j, j = blk
        # fp32 accumulation in the score matmul (matches the Pallas forward,
        # which casts to fp32 before the MXU dot): bf16-rounded scores here
        # would bias the backward's recomputed softmax.
        s = jnp.einsum(
            "bqd,bkd->bqk", q, k_j, preferred_element_type=jnp.float32
        ) * scale
        if masked:
            cols = j * block_k + jnp.arange(block_k)
            mask = _ref_block_mask(
                rows, cols, causal=causal, window=window,
                kv_offset=kv_offset, qseg=qseg,
                kseg_j=kseg_j if segs is not None else None,
            )
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bqk,bkd->bqd", p, v_j.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((bh, s_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, s_q), jnp.float32)
    acc0 = jnp.zeros((bh, s_q, d), jnp.float32)
    (m, l, acc), _ = lax.scan(
        step, (m0, l0, acc0), (kb, vb, ksegb, jnp.arange(nk))
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return o, lse


def _blockwise_bwd_ref(q, k, v, o, lse, do, *, scale, causal, block_k,
                       dlse=None, window=None, kv_offset=0, segs=None):
    """Flash backward: recompute per-block p from lse; O(S·block) memory."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    nk = s_k // block_k
    kb = k.reshape(bh, nk, block_k, d).transpose(1, 0, 2, 3)
    vb = v.reshape(bh, nk, block_k, d).transpose(1, 0, 2, 3)
    rows = jnp.arange(s_q)
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)  # [BH, Sq]
    if dlse is not None:
        # lse-cotangent folds into the same p∘(·) term as delta (see the
        # Pallas dq kernel); keeping them combined avoids a second pass.
        delta = delta - dlse.astype(jnp.float32)
    qseg = None
    ksegb = jnp.zeros((nk, bh, block_k), jnp.float32)
    if segs is not None:
        qseg, kseg = segs
        ksegb = kseg.reshape(bh, nk, block_k).transpose(1, 0, 2)
    masked = causal or window is not None or segs is not None

    def step(dq_acc, blk):
        k_j, v_j, kseg_j, j = blk
        s = jnp.einsum(
            "bqd,bkd->bqk", q, k_j, preferred_element_type=jnp.float32
        ) * scale
        if masked:
            cols = j * block_k + jnp.arange(block_k)
            mask = _ref_block_mask(
                rows, cols, causal=causal, window=window,
                kv_offset=kv_offset, qseg=qseg,
                kseg_j=kseg_j if segs is not None else None,
            )
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # [BH, Sq, bk]
        if masked:
            # all-masked rows carry lse ≈ NEG_INF: exp(s − lse) would
            # resurrect their masked entries as 1.
            p = jnp.where(mask, p, 0.0)
        dv_j = jnp.einsum("bqk,bqd->bkd", p, do32)
        dp = jnp.einsum("bqd,bkd->bqk", do32, v_j.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, k_j.astype(jnp.float32))
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((bh, s_q, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = lax.scan(
        step, dq0, (kb, vb, ksegb, jnp.arange(nk))
    )
    dk = dk_blocks.transpose(1, 0, 2, 3).reshape(bh, s_k, d)
    dv = dv_blocks.transpose(1, 0, 2, 3).reshape(bh, s_k, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Skip accounting (bench/reporting)
# ---------------------------------------------------------------------------
def block_skip_stats(s_q: int, s_k: int, block_q: int, block_k: int, *,
                     causal: bool = True, window: Optional[int] = None,
                     kv_offset: int = 0) -> Tuple[int, int]:
    """(live_blocks, total_blocks) of the blocked forward grid — the pure
    numpy mirror of `_mask_dispatch`'s liveness predicate, so the bench can
    report the causal-skip ratio without running a kernel. On the mono
    path the blocks are the forward kernel's row chunks by as many keys."""
    block_q = fit_block(s_q, block_q)
    block_k = fit_block(s_k, block_k)
    if _mono_ok(s_q, s_k, block_q, block_k, window=window, kv_offset=kv_offset):
        chunks = _mono_chunks(s_q, s_k, causal, _MONO_CHUNK_FWD)
        side = chunks[0][1]  # rows of a whole chunk; s_q when there is one
        return (sum(-(-k1 // side) for _, _, k1 in chunks),
                len(chunks) * -(-s_k // side))
    nq = -(-s_q // block_q)
    nk = -(-s_k // block_k)
    if not causal and window is None:
        return nq * nk, nq * nk
    live = 0
    for i in range(nq):
        first_q = i * block_q + kv_offset
        last_q = first_q + block_q - 1
        for j in range(nk):
            first_k = j * block_k
            last_k = first_k + block_k - 1
            ok = True
            if causal:
                ok = ok and first_k <= last_q
            if window is not None:
                ok = ok and last_k >= first_q - (window - 1)
            live += int(ok)
    return live, nq * nk


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------
def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, segs, scale, causal, block_q, block_k, window,
               kv_offset):
    """Differentiable (o, lse): the lse cotangent feeds the ds term in the
    backward (ring attention differentiates through its partial-softmax
    merge, which weights partials by exp(lse_i − lse_total)). `segs` is
    None or an ([BH, Sq], [BH, Sk]) fp32 pair; its cotangent is zero."""
    return _flash_core(q, k, v, segs, scale, causal, block_q, block_k,
                       window, kv_offset)


def _flash_core(q, k, v, segs, scale, causal, block_q, block_k, window,
                kv_offset):
    if _use_pallas():
        return _flash_fwd_pallas(
            q, k, v, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window, kv_offset=kv_offset, segs=segs,
            interpret=False,
        )
    return _blockwise_fwd_ref(
        q, k, v, scale=scale, causal=causal, block_k=block_k, window=window,
        kv_offset=kv_offset, segs=segs,
    )


def _flash_lse_fwd(q, k, v, segs, scale, causal, block_q, block_k, window,
                   kv_offset):
    o, lse = _flash_core(q, k, v, segs, scale, causal, block_q, block_k,
                         window, kv_offset)
    return (o, lse), (q, k, v, segs, o, lse)


def _flash_lse_bwd(scale, causal, block_q, block_k, window, kv_offset, res,
                   cts):
    q, k, v, segs, o, lse = res
    do, dlse = cts
    if _use_pallas():
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, o, lse, do, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, dlse=dlse, window=window,
            kv_offset=kv_offset, segs=segs,
        )
    else:
        dq, dk, dv = _blockwise_bwd_ref(
            q, k, v, o, lse, do, scale=scale, causal=causal, block_k=block_k,
            dlse=dlse, window=window, kv_offset=kv_offset, segs=segs,
        )
    dsegs = None if segs is None else jax.tree.map(jnp.zeros_like, segs)
    return dq, dk, dv, dsegs


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# -- the monolithic path: heads where the projections leave them ------------
def _mono_shape(ops, h):
    """(B, head width, S_q, S_k, fused) of `_mono_lse`'s operands."""
    b, s_q = ops[0].shape[0], ops[0].shape[-1]
    return b, ops[0].shape[-2] // h, s_q, ops[-1].shape[-1], len(ops) == 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _mono_lse(ops, h, scale, causal):
    """Differentiable (o^T, lse) of the monolithic kernels (on the chip
    only: off it every call takes `_flash_lse`'s reference). `ops` is
    (q^T, k^T, v^T), each [B, h*D, S] with the sequence on the lanes, or
    the one-tuple (qkv^T,) [B, 3, h*D, S] of a fused projection, which the
    kernels then read, and whose gradient they write, in place. That is
    where XLA's TPU layouts put what a `bsd,dthk->bsthk` projection writes
    and a `bshk,hkd->bsd` one reads, so nothing is copied on the way in or
    out, forward or backward; the residuals are the operands themselves.
    o^T is [B, h*D, S_q], lse [B*h, S_q] fp32."""
    return _mono_fwd_pallas(ops, h=h, scale=scale, causal=causal,
                            interpret=False)


def _mono_lse_fwd(ops, h, scale, causal):
    ot, lse = _mono_fwd_pallas(ops, h=h, scale=scale, causal=causal,
                               interpret=False)
    return (ot, lse), (ops, ot, lse)


def _mono_lse_bwd(h, scale, causal, res, cts):
    ops, ot, lse = res
    dot, dlse = cts
    return (tuple(_mono_bwd_pallas(ops, ot, lse, dot, dlse, h=h, scale=scale,
                                   causal=causal, interpret=False)),)


_mono_lse.defvjp(_mono_lse_fwd, _mono_lse_bwd)


def _mono_fwd_pallas(ops, *, h, scale, causal, interpret):
    """(o^T [B, h*D, S_q], lse [B*h, S_q]) of `_mono_lse`'s operands."""
    b, d, s_q, s_k, fused = _mono_shape(ops, h)
    ot, lse = _mono_fwd_fn(b, h, d, s_q, s_k, fused, ops[0].dtype, scale,
                           causal, interpret)(*ops)
    return ot, lse.reshape(b * h, s_q)


def _mono_bwd_pallas(ops, ot, lse, dot, dlse, *, h, scale, causal, interpret):
    """The gradients of `_mono_lse`'s operands, in their form."""
    b, d, s_q, s_k, fused = _mono_shape(ops, h)
    # rowsum(do ∘ o), a head's d rows at a time: [B*h, 1, S_q]
    delta = jnp.sum(
        (dot.astype(jnp.float32) * ot.astype(jnp.float32)).reshape(
            b * h, d, s_q), axis=1, keepdims=True)
    vec = (b * h, 1, s_q)
    return _mono_bwd_fn(
        b, h, d, s_q, s_k, fused, ops[0].dtype, scale, causal, interpret,
    )(*ops, dot, lse.reshape(vec), delta,
      dlse.astype(jnp.float32).reshape(vec))


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    kv_offset: int = 0,
) -> jax.Array:
    """Fused attention; q/k/v: [B, S, H, D] (same layout as ring/ulysses).

    Heads fold into the grid's batch dimension; block sizes clamp to the
    sequence length (and must divide it). Delegates to flash_attention_lse
    (one shape contract); XLA drops the unused lse output.
    """
    o, _ = flash_attention_lse(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        window=window, segment_ids=segment_ids, kv_segment_ids=kv_segment_ids,
        kv_offset=kv_offset,
    )
    return o


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    kv_offset: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """flash_attention that also returns the log-sum-exp per query.

    q/k/v: [B, S, H, D] → (o [B, Sq, H, D], lse [B, Sq, H] fp32). Both
    outputs are differentiable — this is the inner kernel for ring
    attention, whose cross-device merge needs (o, lse) partials.

    window: sliding-window size W (requires causal) — query position p
    attends key positions in (p − W, p]. Blocks fully outside the band
    are skipped (compute AND DMA).
    segment_ids / kv_segment_ids: [B, Sq] / [B, Sk] int ids; attention
    only within equal ids (packed sequences). kv_segment_ids defaults to
    segment_ids (requires s_q == s_k). A query row whose segment matches
    no key gets o = 0 and lse ≈ −1e30.
    kv_offset: global offset of q positions relative to k positions —
    query row r sits at absolute position kv_offset + r in the key frame.
    Ring attention uses this to express cross-device hops; a kv-cache
    decode layout uses it to causal-mask a short q against a long k.
    """
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if kv_offset < 0:
        raise ValueError(f"kv_offset must be >= 0, got {kv_offset}")
    if window is not None:
        if not causal:
            raise ValueError("window (sliding-window) requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if causal and kv_offset == 0 and s_q != s_k:
        # The causal mask top-left aligns sequences (row i sees keys <= i at
        # absolute offset 0), which silently drops the K/V tail in decode /
        # kv-cache layouts; those pass the explicit kv_offset instead.
        raise ValueError(
            f"causal flash attention requires s_q == s_k, got ({s_q}, {s_k})"
            " — pass kv_offset for bottom-aligned decode layouts"
        )
    if kv_segment_ids is None and segment_ids is not None and s_q != s_k:
        raise ValueError(
            "segment_ids with s_q != s_k needs explicit kv_segment_ids"
        )
    if kv_segment_ids is not None and segment_ids is None:
        raise ValueError(
            "kv_segment_ids without segment_ids would be silently ignored; "
            "pass both (q-side ids are required to build the mask)"
        )
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    if s_q % block_q or s_k % block_k:
        raise ValueError(
            f"seq lengths ({s_q}, {s_k}) must be divisible by blocks "
            f"({block_q}, {block_k})"
        )

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    def fold_seg(seg, s):
        if seg.shape != (b, s):
            raise ValueError(
                f"segment ids must be [batch, seq] = ({b}, {s}), "
                f"got {seg.shape}"
            )
        seg = seg.astype(jnp.float32)
        return jnp.broadcast_to(seg[:, None, :], (b, h, s)).reshape(b * h, s)

    segs = None
    if segment_ids is not None:
        kv_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
        segs = (fold_seg(segment_ids, s_q), fold_seg(kv_seg, s_k))

    if _use_pallas() and segs is None and _mono_ok(
            s_q, s_k, block_q, block_k, window=window, kv_offset=kv_offset,
    ) and _mono_tiles(d, q.dtype, k.dtype, v.dtype):
        # One program a head, K/V resident; the dead upper triangle is
        # skipped inside it by static row chunks (`_mono_chunks`). Skipping
        # it from outside lost: two pallas calls with XLA glue
        # (slice/concat/pad), and a 2-band grid with `pl.when` dispatch,
        # each cost more than the quarter they saved. The autotuner still
        # probes this candidate against the blocked ones.
        ot, lse = _mono_lse(
            tuple(x.transpose(0, 2, 3, 1).reshape(b, h * d, x.shape[1])
                  for x in (q, k, v)), h, scale, causal)
        o = ot.reshape(b, h, d, s_q).transpose(0, 3, 1, 2)
    else:
        o, lse = _flash_lse(
            fold(q), fold(k), fold(v), segs, scale, causal, block_q, block_k,
            window, kv_offset,
        )
        o = o.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
    lse = lse.reshape(b, h, s_q).transpose(0, 2, 1)
    return o, lse


def flash_attention_qkv(
    qkv: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """`flash_attention` of a fused projection qkv [B, S, 3, H, D] →
    o [B, S, H, D]. Where the monolithic kernels take the call they read
    q, k and v out of the one array in place and hand back one gradient
    for it, so neither the three slices nor their gradient's assembly
    exist as passes over memory; any other call slices and goes through
    `flash_attention`."""
    b, s, three, h, d = qkv.shape
    assert three == 3, qkv.shape
    bq, bk = min(block_q, s), min(block_k, s)
    if _use_pallas() and segment_ids is None and _mono_ok(
            s, s, bq, bk, window=window) and _mono_tiles(d, qkv.dtype):
        ot, _ = _mono_lse(
            (qkv.transpose(0, 2, 3, 4, 1).reshape(b, 3, h * d, s),), h,
            scale if scale is not None else 1.0 / (d ** 0.5), causal)
        return ot.reshape(b, h, d, s).transpose(0, 3, 1, 2)
    return flash_attention(
        qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, window=window,
        segment_ids=segment_ids,
    )
