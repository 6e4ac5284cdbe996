"""In-kernel paged attention (ops/paged_attention.py): exact-parity
sweeps against the gather+flash decode path and the dense reference,
page-table churn / fragmentation drills, and the geometry/validation
contract. Everything runs the kernel in Pallas interpret mode so the
whole file is tier-1 on CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from determined_tpu.ops.flash_attention import fit_block, flash_attention
from determined_tpu.ops.paged_attention import (
    LANE_GRANULE,
    default_paged_block_h,
    paged_attention,
    paged_pages_read,
)
from determined_tpu.parallel.ring import reference_attention
from determined_tpu.serving.kv_cache import PagePool


def _pool_state(rng, *, num_pages, page_size, n_heads, head_dim, batch,
                pages_per_slot, lengths, active, dtype=np.float32,
                page_perm=None):
    """Random pool K/V + page tables. `page_perm` (scattered page order)
    defaults to a shuffle of the allocatable pages, so tables are never
    contiguous in the pool — the geometry the kernel must get right."""
    kp = rng.normal(size=(num_pages, page_size, n_heads, head_dim))
    vp = rng.normal(size=(num_pages, page_size, n_heads, head_dim))
    if page_perm is None:
        page_perm = rng.permutation(np.arange(1, num_pages))
    pt = np.zeros((batch, pages_per_slot), np.int32)
    need = batch * pages_per_slot
    assert need <= len(page_perm), "test geometry: pool too small"
    pt[:, :] = page_perm[:need].reshape(batch, pages_per_slot)
    return (
        jnp.asarray(kp.astype(dtype)), jnp.asarray(vp.astype(dtype)),
        jnp.asarray(pt), jnp.asarray(np.asarray(lengths, np.int32)),
        jnp.asarray(np.asarray(active, np.int32)),
    )


def _gather_flash(q, kp, vp, pt, lengths, active, *, block_k):
    """GPT.decode_kv's gather path with one live row per slot, verbatim
    geometry: the committed window (positions < lengths) gathered
    contiguous from the pool, the row's own K/V (in the pool at position
    lengths) concatenated behind it at kv_offset = S_max, segment ids
    trimming each slot's dead tail, the pad rows and inactive slots
    entirely."""
    b, qr = q.shape[:2]
    ps = kp.shape[1]
    s_max = pt.shape[1] * ps
    k_full = kp[pt].reshape(b, s_max, *kp.shape[2:])
    v_full = vp[pt].reshape(b, s_max, *vp.shape[2:])

    def with_tail(full):
        fresh = full[jnp.arange(b), lengths][:, None]
        pad = jnp.zeros((b, qr - 1, *full.shape[2:]), full.dtype)
        return jnp.concatenate([full, fresh, pad], axis=1)

    live = active[:, None] != 0
    tail_live = (jnp.arange(qr)[None, :] < 1) & live
    kv_seg = jnp.concatenate(
        [(jnp.arange(s_max)[None, :] < lengths[:, None]) & live, tail_live],
        axis=1,
    ).astype(jnp.int32)
    q_seg = jnp.where(tail_live, 1, 2).astype(jnp.int32)
    return flash_attention(
        q, with_tail(k_full), with_tail(v_full), causal=True,
        kv_offset=s_max, segment_ids=q_seg, kv_segment_ids=kv_seg,
        block_q=qr, block_k=fit_block(s_max + qr, block_k),
    )


def _dense_rows(q, kp, vp, pt, lengths, active):
    """Per-slot dense reference: the real query row attends ALL of its
    live cache positions (softmax over live keys — reference_attention
    with causal=False over exactly the live window)."""
    out = []
    kp_n, vp_n, pt_n = np.asarray(kp), np.asarray(vp), np.asarray(pt)
    ps = kp_n.shape[1]
    for b in range(q.shape[0]):
        if not int(np.asarray(active)[b]):
            out.append(np.zeros(q.shape[2:], np.float32))
            continue
        n = int(np.asarray(lengths)[b]) + 1
        pages = pt_n[b, : -(-n // ps)]
        kf = kp_n[pages].reshape(-1, *kp_n.shape[2:])[:n]
        vf = vp_n[pages].reshape(-1, *vp_n.shape[2:])[:n]
        o = reference_attention(
            jnp.asarray(q)[b:b + 1, :1], jnp.asarray(kf)[None],
            jnp.asarray(vf)[None], causal=False,
        )
        out.append(np.asarray(o, np.float32)[0, 0])
    return np.stack(out)


class TestParityGrid:
    @pytest.mark.parametrize("page_size", [8, 16])
    @pytest.mark.parametrize("occupancy", ["partial", "full"])
    def test_paged_vs_gather_vs_reference(self, page_size, occupancy):
        """The tentpole invariant: across page size × slot occupancy ×
        ragged lengths, the paged kernel, the gather+flash path, and the
        dense reference agree on the real query row."""
        # Deterministic seed: str hash() is PYTHONHASHSEED-salted, which
        # would make any tolerance failure unreproducible across runs.
        rng = np.random.default_rng(
            page_size * 131 + {"partial": 0, "full": 1}[occupancy]
        )
        B, P, H, Dh, qr = 4, 4, 4, 32, 3
        num_pages = B * P + 5
        s_max = P * page_size
        lengths = np.array(
            [0, page_size + 1, s_max // 2 - 1, s_max - 1], np.int32
        )
        active = (
            np.array([1, 0, 1, 0], np.int32) if occupancy == "partial"
            else np.ones((B,), np.int32)
        )
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=num_pages, page_size=page_size, n_heads=H,
            head_dim=Dh, batch=B, pages_per_slot=P, lengths=lengths,
            active=active,
        )
        q = jnp.asarray(
            rng.normal(size=(B, qr, H, Dh)).astype(np.float32)
        )
        o_paged = np.asarray(paged_attention(
            q, kp, vp, pt, lengths, active, interpret=True
        ))
        o_gather = np.asarray(_gather_flash(
            q, kp, vp, pt, lengths, active, block_k=page_size
        ))
        dense = _dense_rows(q, kp, vp, pt, lengths, active)
        np.testing.assert_allclose(
            o_paged[:, 0], o_gather[:, 0], rtol=0, atol=2e-6
        )
        np.testing.assert_allclose(o_paged[:, 0], dense, rtol=0, atol=2e-5)
        # inactive slots output exactly zero on both paths
        for b in range(B):
            if not int(np.asarray(active)[b]):
                assert np.all(o_paged[b] == 0)
                assert np.all(np.asarray(o_gather)[b, 0] == 0)

    def test_single_page_bitwise_vs_flash_kernel(self):
        """A partial (length-masked) page runs the SAME masked op
        sequence as the PALLAS flash kernel (interpret mode — the
        program that runs on TPU, rather than the CPU scan reference
        `flash_attention` dispatches to off-TPU): outputs bitwise-equal.
        Fully-live interior pages intentionally drop the mask work the
        flash path spends on segment ids — there, and across multi-block
        accumulation, cross-program XLA fusion bounds identity at ~1 ulp
        (the grid test pins that envelope)."""
        from determined_tpu.ops.flash_attention import _flash_fwd_pallas

        rng = np.random.default_rng(7)
        ps, H, Dh, B = 16, 4, 32, 2
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=8, page_size=ps, n_heads=H, head_dim=Dh,
            batch=B, pages_per_slot=1, lengths=[3, ps - 2], active=[1, 1],
        )
        q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)).astype(np.float32))
        o_paged = np.asarray(paged_attention(
            q, kp, vp, pt, lengths, active, interpret=True
        ))
        s_max = ps
        k_full = kp[pt].reshape(B, s_max, H, Dh)
        v_full = vp[pt].reshape(B, s_max, H, Dh)
        kv_seg = (
            (jnp.arange(s_max)[None, :] <= lengths[:, None])
        ).astype(jnp.float32)
        q_seg = jnp.ones((B, 1), jnp.float32)

        def fold(x):
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(
                B * H, x.shape[1], Dh
            )

        def fold_seg(s):
            return jnp.broadcast_to(
                s[:, None, :], (B, H, s.shape[1])
            ).reshape(B * H, s.shape[1])

        o_fl, _ = _flash_fwd_pallas(
            fold(q), fold(k_full), fold(v_full), scale=1.0 / Dh ** 0.5,
            causal=True, block_q=1, block_k=ps, interpret=True,
            kv_offset=s_max - 1, segs=(fold_seg(q_seg), fold_seg(kv_seg)),
        )
        o_fl = np.asarray(o_fl).reshape(B, H, 1, Dh).transpose(0, 2, 1, 3)
        assert np.array_equal(o_paged[:, 0], o_fl[:, 0])

    def test_dead_pages_never_read(self):
        """Poisoning every non-live pool page (huge magnitudes) must not
        move the output AT ALL — the proof that dead pages are neither
        DMA'd into the softmax nor computed."""
        rng = np.random.default_rng(3)
        ps, B, P, H, Dh = 8, 3, 4, 2, 16
        lengths = [2, ps * 2 - 1, ps * 3]
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=B * P + 3, page_size=ps, n_heads=H, head_dim=Dh,
            batch=B, pages_per_slot=P, lengths=lengths, active=[1, 1, 1],
        )
        q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)).astype(np.float32))
        o = np.asarray(paged_attention(
            q, kp, vp, pt, lengths, active, interpret=True
        ))
        live = set()
        for b in range(B):
            n = int(np.asarray(lengths)[b]) + 1
            live |= set(np.asarray(pt)[b, : -(-n // ps)].tolist())
        kp_n, vp_n = np.asarray(kp).copy(), np.asarray(vp).copy()
        for pg in range(kp_n.shape[0]):
            if pg not in live:
                kp_n[pg] = 1e6
                vp_n[pg] = -1e6
        o_poisoned = np.asarray(paged_attention(
            q, jnp.asarray(kp_n), jnp.asarray(vp_n), pt, lengths, active,
            interpret=True,
        ))
        assert np.array_equal(o, o_poisoned)

    def test_block_h_invariance(self):
        """Head grouping is a pure tiling choice: every divisor of H
        gives bitwise the same output."""
        rng = np.random.default_rng(4)
        ps, B, P, H, Dh = 8, 2, 3, 4, 16
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=B * P + 2, page_size=ps, n_heads=H, head_dim=Dh,
            batch=B, pages_per_slot=P, lengths=[5, 2 * ps], active=[1, 1],
        )
        q = jnp.asarray(rng.normal(size=(B, 2, H, Dh)).astype(np.float32))
        outs = [
            np.asarray(paged_attention(
                q, kp, vp, pt, lengths, active, block_h=bh, interpret=True
            ))
            for bh in (1, 2, 4)
        ]
        assert np.array_equal(outs[0], outs[1])
        assert np.array_equal(outs[0], outs[2])

    def test_qpad_rows_do_not_disturb_row0(self):
        """TPU lane padding: extra query rows change nothing about the
        real row's output."""
        rng = np.random.default_rng(5)
        ps, H, Dh = 8, 2, 16
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=6, page_size=ps, n_heads=H, head_dim=Dh,
            batch=1, pages_per_slot=2, lengths=[ps + 3], active=[1],
        )
        q1 = jnp.asarray(rng.normal(size=(1, 1, H, Dh)).astype(np.float32))
        q8 = jnp.concatenate(
            [q1, jnp.zeros((1, 7, H, Dh), q1.dtype)], axis=1
        )
        o1 = np.asarray(paged_attention(
            q1, kp, vp, pt, lengths, active, interpret=True
        ))
        o8 = np.asarray(paged_attention(
            q8, kp, vp, pt, lengths, active, interpret=True
        ))
        # Not bitwise: one query row is a matrix-vector product, eight a
        # matrix-matrix one, and the CPU interpreter's two routines need
        # not sum in the same order.
        np.testing.assert_allclose(o1[:, 0], o8[:, 0], rtol=1e-6, atol=1e-7)


class TestFragmentation:
    def test_fragmented_free_list_parity(self):
        """Fragmentation drill: alloc/free interleave until the free
        list is maximally scattered, then serve a batch whose page
        tables come straight out of that shuffled free list — parity
        with the gather path must hold on arbitrary page identity."""
        rng = np.random.default_rng(11)
        ps, B, P, H, Dh = 8, 4, 3, 2, 16
        num_pages = 41
        pool = PagePool(num_pages)
        # Interleave: grab the whole pool in small stripes, free every
        # other stripe, re-alloc half-sized, repeat — the free list ends
        # up with no two adjacent page ids in order.
        stripes = [pool.alloc(4) for _ in range(10)]
        for s in stripes[::2]:
            pool.free(s)
        small = [pool.alloc(2) for _ in range(8)]
        for s in stripes[1::2]:
            pool.free(s)
        for s in small:
            pool.free(s)
        free_order = list(pool._free)
        assert free_order != sorted(free_order), "drill failed to scatter"
        tables = [pool.alloc(P) for _ in range(B)]
        pt = np.asarray(tables, np.int32)
        kp = jnp.asarray(
            rng.normal(size=(num_pages, ps, H, Dh)).astype(np.float32)
        )
        vp = jnp.asarray(
            rng.normal(size=(num_pages, ps, H, Dh)).astype(np.float32)
        )
        q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)).astype(np.float32))
        lengths = jnp.asarray(
            np.array([1, ps, 2 * ps - 1, 3 * ps - 1], np.int32)
        )
        active = jnp.ones((B,), jnp.int32)
        o_paged = np.asarray(paged_attention(
            q, kp, vp, jnp.asarray(pt), lengths, active, interpret=True
        ))
        o_gather = np.asarray(_gather_flash(
            q, kp, vp, jnp.asarray(pt), lengths, active, block_k=ps
        ))
        np.testing.assert_allclose(
            o_paged[:, 0], o_gather[:, 0], rtol=0, atol=2e-6
        )


class TestGeometryContract:
    def test_lane_granule_matches_config_mirror(self):
        from determined_tpu.serving.config import PAGE_LANE_GRANULE

        assert PAGE_LANE_GRANULE == LANE_GRANULE

    def test_misaligned_page_size_rejected_outside_interpret(self):
        """The compiled TPU kernel refuses a misaligned page up front —
        the config-time validation mirrors this; neither lets it reach
        Mosaic as a shape crash."""
        rng = np.random.default_rng(0)
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=4, page_size=24, n_heads=2, head_dim=16,
            batch=1, pages_per_slot=2, lengths=[3], active=[1],
        )
        q = jnp.asarray(rng.normal(size=(1, 1, 2, 16)).astype(np.float32))
        with pytest.raises(ValueError, match="lane granule"):
            paged_attention(q, kp, vp, pt, lengths, active, interpret=False)

    def test_block_h_must_divide_heads(self):
        rng = np.random.default_rng(0)
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=4, page_size=8, n_heads=4, head_dim=16,
            batch=1, pages_per_slot=2, lengths=[3], active=[1],
        )
        q = jnp.asarray(rng.normal(size=(1, 1, 4, 16)).astype(np.float32))
        with pytest.raises(ValueError, match="divide"):
            paged_attention(
                q, kp, vp, pt, lengths, active, block_h=3, interpret=True
            )

    def test_default_block_h_respects_vmem_budget(self):
        # small pages: whole head stack fits
        assert default_paged_block_h(12, 64, 128, jnp.bfloat16) == 12
        # monstrous pages: falls back toward fewer heads per step, but
        # always a divisor of H
        bh = default_paged_block_h(12, 128, 8192, jnp.float32)
        assert 12 % bh == 0 and bh < 12

    def test_pages_read_mirror(self):
        lengths = np.array([0, 15, 16, 47], np.int32)
        active = np.array([1, 1, 0, 1], bool)
        # page_size 16: 1 + 1 + (inactive) + 3
        assert paged_pages_read(lengths, active, 16) == 5


class TestQLens:
    """The speculative-verify extension: `q_lens[b]` live query rows per
    slot, row r attending the committed window PLUS the first r draft
    positions (cols ≤ lengths[b] + r)."""

    def test_qlens_ones_bitwise_equals_none(self):
        """q_lens of all-ones is EXACTLY the plain decode geometry — the
        spec-capable call must be bitwise identical to the legacy one,
        which is what lets one compiled function serve mixed batches."""
        rng = np.random.default_rng(21)
        ps, B, P, H, Dh = 8, 3, 3, 2, 16
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=B * P + 2, page_size=ps, n_heads=H, head_dim=Dh,
            batch=B, pages_per_slot=P, lengths=[2, ps, 2 * ps - 1],
            active=[1, 0, 1],
        )
        q = jnp.asarray(rng.normal(size=(B, 2, H, Dh)).astype(np.float32))
        o_none = np.asarray(paged_attention(
            q, kp, vp, pt, lengths, active, interpret=True
        ))
        o_ones = np.asarray(paged_attention(
            q, kp, vp, pt, lengths, active,
            q_lens=jnp.ones((B,), jnp.int32), interpret=True,
        ))
        assert np.array_equal(o_none, o_ones)

    def test_multirow_verify_vs_dense_reference(self):
        """Ragged q_lens across a batch (1, full draft, mid) against a
        per-row dense reference: row r sees exactly lengths[b] + r + 1
        keys. Draft rows cross page boundaries on purpose."""
        rng = np.random.default_rng(22)
        ps, B, P, H, Dh, Q = 8, 3, 4, 2, 16, 5
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=B * P + 2, page_size=ps, n_heads=H, head_dim=Dh,
            batch=B, pages_per_slot=P,
            # slot 1's draft spans a page edge (ps-2 .. ps+2)
            lengths=[3, ps - 2, 2 * ps], active=[1, 1, 1],
        )
        q_lens = jnp.asarray(np.array([1, Q, 3], np.int32))
        q = jnp.asarray(rng.normal(size=(B, Q, H, Dh)).astype(np.float32))
        o = np.asarray(paged_attention(
            q, kp, vp, pt, lengths, active, q_lens=q_lens, interpret=True
        ))
        kp_n, vp_n, pt_n = np.asarray(kp), np.asarray(vp), np.asarray(pt)
        for b in range(B):
            for r in range(int(np.asarray(q_lens)[b])):
                n = int(np.asarray(lengths)[b]) + r + 1
                pages = pt_n[b, : -(-n // ps)]
                kf = kp_n[pages].reshape(-1, H, Dh)[:n]
                vf = vp_n[pages].reshape(-1, H, Dh)[:n]
                ref = np.asarray(reference_attention(
                    jnp.asarray(q)[b:b + 1, r:r + 1], jnp.asarray(kf)[None],
                    jnp.asarray(vf)[None], causal=False,
                ), np.float32)[0, 0]
                np.testing.assert_allclose(
                    o[b, r], ref, rtol=0, atol=2e-5,
                    err_msg=f"slot {b} draft row {r}",
                )

    def test_dead_pages_never_read_with_qlens(self):
        """Poison every page past each slot's lengths + q_lens - 1
        horizon: outputs on the live rows must not move — the draft
        window widens the read set by exactly the draft, nothing more."""
        rng = np.random.default_rng(23)
        ps, B, P, H, Dh, Q = 8, 2, 4, 2, 16, 4
        lengths = [ps - 2, 2 * ps - 1]
        q_lens = np.array([Q, 2], np.int32)
        kp, vp, pt, lengths, active = _pool_state(
            rng, num_pages=B * P + 3, page_size=ps, n_heads=H, head_dim=Dh,
            batch=B, pages_per_slot=P, lengths=lengths, active=[1, 1],
        )
        q = jnp.asarray(rng.normal(size=(B, Q, H, Dh)).astype(np.float32))
        o = np.asarray(paged_attention(
            q, kp, vp, pt, lengths, active, q_lens=jnp.asarray(q_lens),
            interpret=True,
        ))
        live = set()
        for b in range(B):
            n = int(np.asarray(lengths)[b]) + int(q_lens[b])  # last live +1
            live |= set(np.asarray(pt)[b, : -(-n // ps)].tolist())
        kp_n, vp_n = np.asarray(kp).copy(), np.asarray(vp).copy()
        for pg in range(kp_n.shape[0]):
            if pg not in live:
                kp_n[pg] = 1e6
                vp_n[pg] = -1e6
        o_poisoned = np.asarray(paged_attention(
            q, jnp.asarray(kp_n), jnp.asarray(vp_n), pt, lengths, active,
            q_lens=jnp.asarray(q_lens), interpret=True,
        ))
        for b in range(B):
            m = int(q_lens[b])
            assert np.array_equal(o[b, :m], o_poisoned[b, :m])

    def test_pages_read_mirror_with_qlens(self):
        lengths = np.array([0, 15, 16, 40], np.int32)
        active = np.array([1, 1, 0, 1], bool)
        q_lens = np.array([5, 2, 9, 1], np.int32)
        # page_size 16, last live pos = length + q_len - 1:
        # 4 → 1 page; 16 → 2; inactive → 0; 40 → 3
        assert paged_pages_read(lengths, active, 16, q_lens=q_lens) == 6
        # all-ones q_lens degenerates to the legacy accounting
        ones = np.ones((4,), np.int32)
        assert paged_pages_read(lengths, active, 16, q_lens=ones) == \
            paged_pages_read(lengths, active, 16)


class TestPagedAutotune:
    def test_off_tpu_returns_deterministic_fallback(self, tmp_path):
        from determined_tpu.ops.flash_autotune import tune_paged_block_h

        cache = tmp_path / "tune.json"
        bh = tune_paged_block_h(
            n_heads=4, head_dim=16, page_size=16, num_pages=33,
            pages_per_slot=4, batch=4, q_rows=1, dtype=jnp.float32,
            cache_file=str(cache),
        )
        assert bh == default_paged_block_h(4, 16, 16, jnp.float32)
        assert not cache.exists(), "no probe must run (and cache) off-TPU"
