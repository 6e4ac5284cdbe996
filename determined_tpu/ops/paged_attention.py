"""In-kernel paged attention for KV-cache decode.

The serving engine's decode step used to gather every slot's pages into a
contiguous ``[B, S_max, H, Dh]`` K/V buffer and only then call the flash
kernel — a full HBM round-trip over the entire cache window per generated
token, paid even for slots using a fraction of their page budget. This
kernel removes the round-trip: the page table and per-slot lengths ride in
as *scalar-prefetch* operands (``pltpu.PrefetchScalarGridSpec``), and the
K/V BlockSpec ``index_map`` turns each grid step's page-table entry into
the DMA source directly — the pool is the only K/V layout that ever
exists, and a slot's dead page-table tail costs neither DMA nor compute:

- DMA: dead iterations clamp onto the slot's *last live page* — Pallas
  elides the copy when consecutive grid steps map the same block (the
  same jax-ml remap technique the flash kernels use for causal
  dead-block elision);
- compute: the ``@pl.when`` dispatch never runs the MXU work for a page
  past the slot's live length.

Masking moves inside the kernel with it: the flash gather path expressed
"trim each slot's dead cache tail" as ``kv_offset = S_max − 1`` plus
per-position segment ids materialized every iteration; here a page is
interior (no mask), the length boundary page (element mask
``col ≤ length``), or dead (skipped), decided from the prefetched scalars.

Per-head arithmetic is kept IDENTICAL to ``flash_attention``'s blocked
forward (same op sequence on the same fp32 values), so decode through
this kernel is bitwise-equal to the gather path whenever the gather
path's ``block_k`` equals ``page_size`` — the parity tests pin that.

Layout: the pool is row-major ``[pages, page, H, Dh]``, so the kernel
takes it (and q/o) with heads folded into the minor dimension —
``[pages, page, H·Dh]``, a free reshape — and slices each head's ``Dh``
lanes out of the block. Heads as a second-minor block axis is what the
TPU compiler refuses below the 128-lane tile (GPT-2's 12 × 64: Mosaic
cannot lay out the ``(q_rows, Dh)`` → ``(1, q_rows, 1, Dh)`` store, and
no ``block_h`` but ``H`` passes the block-shape rule); folded, a block's
minor extent is ``block_h·Dh`` and `paged_block_h_ok` is the rule.

``block_h`` (heads per grid step) is the one tunable: more heads per
step amortize each page's DMA across heads at the cost of VMEM
residency. It is sized by ``ops/flash_autotune.tune_paged_block_h``
(pool geometry in the cache key), never by literals at call sites —
``tests/test_flash_block_discipline.py`` enforces that.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

from determined_tpu.ops.flash_attention import NEG_INF


class _LazyPallas:
    """Same deferred-import trick as ops/flash_attention.py: CPU-only
    processes that never run the kernel skip the ~1 s pallas import."""

    def __getattr__(self, name):
        from jax.experimental import pallas

        globals()["pl"] = pallas
        return getattr(pallas, name)


pl = _LazyPallas()

#: K/V pages enter the kernel as ``(page_size, head_dim)`` MXU tiles with
#: ``page_size`` on the lane-tiled axis of the score matrix — the same granule ``fit_block``
#: prefers for flash ``block_k``. A misaligned ``page_size`` must be a
#: named config error (serving/config.py mirrors this constant), not a
#: mid-decode Mosaic shape failure.
LANE_GRANULE = 128

#: The kernel's name in a profiler capture (`name=` on the `pallas_call`).
PAGED_ATTN = "dtpu_paged_attn"

#: VMEM budget for one grid step's resident K+V page group (bytes).
#: Conservative: q/out/softmax scratch ride alongside in ~16 MB of VMEM.
_PAGE_GROUP_VMEM_CAP = 4 * 1024 * 1024


def paged_block_h_fits(block_h: int, head_dim: int, page_size: int,
                       dtype) -> bool:
    """Does a ``block_h``-head K+V page group fit the kernel's VMEM
    budget? The ONE fit predicate — `default_paged_block_h` picks the
    largest fitting divisor and the autotuner filters its candidates
    through the same inequality, so the fallback is in the candidate
    set by construction."""
    itemsize = jnp.dtype(dtype).itemsize
    return (
        2 * page_size * block_h * head_dim * itemsize
        <= _PAGE_GROUP_VMEM_CAP
    )


def paged_block_h_ok(block_h: int, n_heads: int, head_dim: int) -> bool:
    """Does the TPU lowering admit ``block_h`` heads per grid step? The
    block's minor extent is ``block_h · head_dim`` lanes of the folded
    ``H · Dh`` axis: a multiple of the 128-lane tile, or the whole axis."""
    return n_heads % block_h == 0 and (
        block_h == n_heads or (block_h * head_dim) % LANE_GRANULE == 0
    )


def paged_block_h_candidates(n_heads: int, head_dim: int, page_size: int,
                             dtype) -> List[int]:
    """Every ``block_h`` the lowering admits whose K+V page group fits
    the VMEM budget, ascending — the autotuner's candidate set. Never
    empty: ``block_h == n_heads`` is always admissible, and when nothing
    fits the budget the smallest admissible group (least VMEM) stands."""
    ok = [
        h for h in range(1, n_heads + 1)
        if paged_block_h_ok(h, n_heads, head_dim)
    ]
    return [
        h for h in ok if paged_block_h_fits(h, head_dim, page_size, dtype)
    ] or ok[:1]


def default_paged_block_h(n_heads: int, head_dim: int, page_size: int,
                          dtype) -> int:
    """Largest candidate — the deterministic no-probe choice the
    autotuner refines."""
    return paged_block_h_candidates(n_heads, head_dim, page_size, dtype)[-1]


def _page_index(b, hg, j, pt_ref, len_ref, ql_ref, act_ref, *, page_size):
    """Pool page for grid step (slot b, head group hg, page slot j): the
    slot's j-th table entry while live, clamped to its LAST live page
    once dead — consecutive dead steps then map the same block and
    Pallas elides the DMA entirely. With ``q_lens[b]`` query rows the
    slot's last live position is ``length + q_lens − 1`` (row r sits at
    position ``length + r``); at q_lens = 1 this reduces exactly to the
    single-token ``length // page_size``."""
    del hg, act_ref
    # live pages − 1 (length + q_lens live tokens)
    last_live = (len_ref[b] + ql_ref[b] - 1) // page_size
    return pt_ref[b, jnp.minimum(j, last_live)]


def _paged_kernel(pt_ref, len_ref, ql_ref, act_ref, q_ref, k_ref, v_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, scale, page_size,
                  block_h, num_page_slots, q_rows, head_dim):
    """One (slot, head-group, page) step of the paged decode grid.

    Math per head mirrors ops/flash_attention._fwd_kernel exactly (dot →
    mask → running max → exp → correction → accumulate), with the page's
    liveness regime standing in for the band dispatch. Query row r sits
    at position ``length + r`` (speculative verify: row 0 is the last
    committed token, rows 1..q_lens−1 the draft), so its visibility
    boundary is ``col ≤ length + r``; rows past ``q_lens − 1`` are lane
    padding clamped onto the last real row's mask (their output is
    dropped by the caller). At q_lens = 1 every predicate and mask below
    is the plain single-token decode, bit for bit.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]               # row 0's position; length+1 live there
    q_live = ql_ref[b]                # real query rows (≥ 1)
    n_tokens = length + 1             # row 0's visible-token count
    is_active = act_ref[b] != 0
    page_first = j * page_size

    def _compute(edge_masked):
        for h in range(block_h):
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, :, lanes]    # [q_rows, Dh]
            k = k_ref[0, :, lanes]    # [page_size, Dh]
            v = v_ref[0, :, lanes]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                 # [q_rows, page_size] fp32
            if edge_masked:
                cols = page_first + jax.lax.broadcasted_iota(
                    jnp.int32, (q_rows, page_size), 1
                )
                row_i = jax.lax.broadcasted_iota(
                    jnp.int32, (q_rows, page_size), 0
                )
                # per-row boundary: row r sees cols ≤ length + r; padding
                # rows clamp onto the last real row (output dropped).
                mask = cols <= length + jnp.minimum(row_i, q_live - 1)
                s = jnp.where(mask, s, NEG_INF)
            rows = slice(h * q_rows, (h + 1) * q_rows)
            m_prev = m_scr[rows, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            if edge_masked:
                p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[rows, 0:1] = (
                l_scr[rows, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
            )
            acc_scr[rows] = acc_scr[rows] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[rows, 0:1] = m_new

    # Page regimes: interior (every position live for EVERY real row —
    # bounded by row 0, the tightest), the boundary band (per-row element
    # mask; spans up to the last real row's visibility), dead (skip —
    # paired with the index_map clamp above, a dead page costs neither
    # DMA nor compute). At q_lens = 1 the band collapses to the classic
    # single length-boundary page.
    interior = is_active & (page_first + page_size <= n_tokens)
    edge = (
        is_active
        & (page_first < length + q_live)
        & jnp.logical_not(interior)
    )

    @pl.when(interior)
    def _():
        _compute(edge_masked=False)

    @pl.when(edge)
    def _():
        _compute(edge_masked=True)

    @pl.when(j == num_page_slots - 1)
    def _epilogue():
        for h in range(block_h):
            rows = slice(h * q_rows, (h + 1) * q_rows)
            l = l_scr[rows, 0:1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, h * head_dim:(h + 1) * head_dim] = (
                acc_scr[rows] / l_safe
            ).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    active: jax.Array,
    *,
    q_lens: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_h: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention straight over the paged KV pool.

    q: [B, q_rows, H, Dh] — row 0 is the real query (the token at
    position ``lengths[b]``, already written into the pool); extra rows
    are TPU lane padding whose output the caller drops — unless
    ``q_lens`` marks them live (below).
    k_pool/v_pool: [num_pages, page_size, H, Dh] — ONE layer's pool.
    page_table: [B, P] int32 — each slot's pages in order (dead tail
    arbitrary; it is never dereferenced live).
    lengths: [B] int32 — tokens cached BEFORE this iteration's token;
    the slot therefore has ``lengths[b] + q_lens[b]`` live positions.
    active: [B] bool/int32 — inactive slots read nothing and output 0,
    exactly like the gather path's unmatched segment ids.
    q_lens: [B] int32 — real query rows per slot (speculative verify:
    row r is the token at position ``lengths[b] + r``, already written
    into the pool, and sees exactly positions 0..lengths[b]+r — the
    bottom-aligned per-row boundary). Default (None) is all-ones: the
    plain single-token decode, whose masks/regimes/DMA schedule this
    reduces to bit for bit.

    → o [B, q_rows, H, Dh] (pool dtype). Forward-only — decode never
    differentiates. Every shape is static in (B, P, pool geometry).
    """
    from jax.experimental.pallas import tpu as pltpu

    b, q_rows, n_heads, head_dim = q.shape
    if q_lens is None:
        q_lens = jnp.ones((b,), jnp.int32)
    num_pages, page_size, pool_h, pool_d = k_pool.shape
    n_slots, num_page_slots = page_table.shape
    if (pool_h, pool_d) != (n_heads, head_dim):
        raise ValueError(
            f"pool heads/dim {(pool_h, pool_d)} != q {(n_heads, head_dim)}"
        )
    if n_slots != b:
        raise ValueError(f"page_table batch {n_slots} != q batch {b}")
    if not interpret and page_size % LANE_GRANULE:
        raise ValueError(
            f"page_size {page_size} must be a multiple of the flash "
            f"block_k lane granule ({LANE_GRANULE}) for the paged TPU "
            "kernel — serving/config.py validates this at config time"
        )
    if block_h is None:
        block_h = default_paged_block_h(n_heads, head_dim, page_size,
                                        k_pool.dtype)
    if n_heads % block_h:
        raise ValueError(f"block_h {block_h} must divide n_heads {n_heads}")
    if not interpret and not paged_block_h_ok(block_h, n_heads, head_dim):
        raise ValueError(
            f"block_h {block_h} x head_dim {head_dim} is neither a "
            f"multiple of {LANE_GRANULE} lanes nor all {n_heads} heads — "
            "the TPU lowering refuses that block"
        )
    scale = scale if scale is not None else 1.0 / (head_dim ** 0.5)

    kv_map = functools.partial(_page_index, page_size=page_size)

    def head_map(b_, hg, j, pt_ref, len_ref, ql_ref, act_ref):
        del j, pt_ref, len_ref, ql_ref, act_ref
        return (b_, 0, hg)

    def kv_block_map(b_, hg, j, pt_ref, len_ref, ql_ref, act_ref):
        return (
            kv_map(b_, hg, j, pt_ref, len_ref, ql_ref, act_ref), 0, hg
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_heads // block_h, num_page_slots),
        in_specs=[
            pl.BlockSpec((1, q_rows, block_h * head_dim), head_map),
            pl.BlockSpec((1, page_size, block_h * head_dim), kv_block_map),
            pl.BlockSpec((1, page_size, block_h * head_dim), kv_block_map),
        ],
        out_specs=pl.BlockSpec((1, q_rows, block_h * head_dim), head_map),
        scratch_shapes=[
            pltpu.VMEM((block_h * q_rows, 128), jnp.float32),   # m
            pltpu.VMEM((block_h * q_rows, 128), jnp.float32),   # l
            pltpu.VMEM((block_h * q_rows, head_dim), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size, block_h=block_h,
        num_page_slots=num_page_slots, q_rows=q_rows, head_dim=head_dim,
    )
    # Heads fold into the minor dimension (free: all three are row-major
    # with [H, Dh] innermost) — see the module docstring.
    folded = n_heads * head_dim
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, q_rows, folded), k_pool.dtype),
        interpret=interpret,
        name=PAGED_ATTN,
    )(
        page_table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        active.astype(jnp.int32),
        q.reshape(b, q_rows, folded),
        k_pool.reshape(num_pages, page_size, folded),
        v_pool.reshape(num_pages, page_size, folded),
    ).reshape(b, q_rows, n_heads, head_dim)


def paged_pages_read(lengths, active, page_size: int, q_lens=None) -> int:
    """Pool pages a decode iteration actually reads (live pages summed
    over active slots) — the host-side mirror of the kernel's liveness
    predicate, feeding ``dtpu_serving_kv_pages_read_total``. With
    ``q_lens`` (speculative verify rows) a slot's live window extends to
    ``lengths + q_lens − 1``; the default mirrors the plain decode."""
    import numpy as np

    lengths = np.asarray(lengths)
    active = np.asarray(active).astype(bool)
    if q_lens is None:
        q_lens = np.ones_like(lengths)
    q_lens = np.asarray(q_lens)
    return int(np.sum(
        np.where(active, (lengths + q_lens - 1) // page_size + 1, 0)
    ))
