"""Flash block-size autotuner: candidate generation, cache behavior, and
the off-TPU no-probe contract."""
import json

import jax
import jax.numpy as jnp
import pytest

from determined_tpu.ops import flash_autotune as fat
from determined_tpu.ops.flash_attention import _MONO_MAX_SCORES


def test_candidates_fitted_and_deduped():
    cands = fat.candidate_blocks(1024, 1024, want_q=1024, want_k=1024)
    assert cands[0] == (1024, 1024)  # caller's wanted pair leads
    assert len(set(cands)) == len(cands)
    for bq, bk in cands:
        assert 1024 % bq == 0 and 1024 % bk == 0
    # mono candidate (block == seq) is in the set at this size
    assert (1024, 1024) in cands


def test_candidates_mono_respects_vmem_cap():
    s = 4096
    assert s * s > _MONO_MAX_SCORES
    cands = fat.candidate_blocks(s, s, want_q=1024, want_k=1024)
    assert (s, s) not in cands


def test_candidates_ragged_sequences():
    # 96 has no 128-multiple divisor: every candidate degrades via
    # fit_block but still divides.
    for bq, bk in fat.candidate_blocks(96, 96):
        assert 96 % bq == 0 and 96 % bk == 0


def test_tune_off_tpu_returns_fitted_want(tmp_path):
    """On the CPU backend no probe runs and no cache is touched — the
    result is the caller's wanted blocks fitted to the sequence (the
    pre-autotuner behavior)."""
    assert jax.default_backend() != "tpu"
    cache = tmp_path / "cache.json"
    got = fat.tune_flash_blocks(
        s_q=96, n_heads=2, head_dim=16, want_q=1024, want_k=512,
        cache_file=str(cache),
    )
    assert got == (96, 96)  # largest divisors of 96 under the wants
    assert not cache.exists()


def test_tune_probes_once_then_caches(tmp_path, monkeypatch):
    """With the backend reporting TPU, the tuner probes every candidate,
    stores the winner, and never probes again for the same key."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class _Dev:
        device_kind = "fake-tpu-v9"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    calls = []

    def fake_probe(bq, bk, **kw):
        calls.append((bq, bk))
        return abs(bq - 64) + abs(bk - 32)  # (64, 32) wins

    monkeypatch.setattr(fat, "_probe_ms", fake_probe)
    cache = tmp_path / "cache.json"
    got = fat.tune_flash_blocks(
        s_q=64, s_k=64, n_heads=2, head_dim=16, want_q=64, want_k=32,
        cache_file=str(cache),
    )
    assert got == (64, 32)
    assert calls  # probed
    data = json.loads(cache.read_text())
    assert list(data.values()) == [[64, 32]]
    key = next(iter(data))
    assert "fake-tpu-v9" in key and f"v{fat.CACHE_VERSION}" in key

    calls.clear()
    again = fat.tune_flash_blocks(
        s_q=64, s_k=64, n_heads=2, head_dim=16, want_q=64, want_k=32,
        cache_file=str(cache),
    )
    assert again == (64, 32)
    assert calls == []  # cache hit, no probe

    # a different mask mode is a different key → probes again
    fat.tune_flash_blocks(
        s_q=64, s_k=64, n_heads=2, head_dim=16, want_q=64, want_k=32,
        window=16, cache_file=str(cache),
    )
    assert calls


def test_tune_env_kill_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("DTPU_FLASH_AUTOTUNE", "0")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    probed = []
    monkeypatch.setattr(
        fat, "_probe_ms", lambda *a, **k: probed.append(1) or 0.0
    )
    got = fat.tune_flash_blocks(
        s_q=128, n_heads=2, head_dim=16, want_q=64, want_k=64,
        cache_file=str(tmp_path / "c.json"),
    )
    assert got == (64, 64)
    assert probed == []


def test_corrupt_cache_degrades_to_probe(tmp_path, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class _Dev:
        device_kind = "fake"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    monkeypatch.setattr(fat, "_probe_ms", lambda bq, bk, **kw: float(bq))
    cache = tmp_path / "cache.json"
    cache.write_text("{not json")
    got = fat.tune_flash_blocks(
        s_q=64, n_heads=2, head_dim=16, want_q=64, want_k=64,
        cache_file=str(cache),
    )
    # smallest block_q among candidates wins under the fake timer
    assert got[0] == min(
        c[0] for c in fat.candidate_blocks(64, 64, 64, 64)
    )
    json.loads(cache.read_text())  # rewritten as valid json


def test_gpt_resolves_blocks_from_config():
    """flash_autotune=False (default) keeps the config constants; the
    resolution is cached on the model instance."""
    from determined_tpu.models.gpt import GPT, tiny

    m = GPT(tiny(seq_len=64))
    assert m._flash_blocks() == (1024, 1024)
    assert m._flash_blocks() is m._resolved_flash_blocks


def test_all_probes_failing_raises_not_cached(tmp_path, monkeypatch):
    """Every candidate failing is an error that carries the compiler's
    message — never an untuned fallback — and pins nothing into the
    on-disk cache."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class _Dev:
        device_kind = "fake"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: no")

    monkeypatch.setattr(fat, "_probe_ms", refuse)
    cache = tmp_path / "cache.json"
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        fat.tune_flash_blocks(
            s_q=64, n_heads=2, head_dim=16, want_q=64, want_k=64,
            cache_file=str(cache),
        )
    assert not cache.exists()


def test_one_probe_failing_loses(tmp_path, monkeypatch):
    """A single refused candidate (VMEM, shape) just loses."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class _Dev:
        device_kind = "fake"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])

    def probe(bq, bk, **kw):
        if (bq, bk) == (256, 256):
            return 1.0
        raise RuntimeError("vmem")

    monkeypatch.setattr(fat, "_probe_ms", probe)
    cleared = []
    monkeypatch.setattr(jax, "clear_caches", lambda: cleared.append(1))
    got = fat.tune_flash_blocks(
        s_q=1024, n_heads=2, head_dim=16, cache_file=str(tmp_path / "c.json"),
    )
    assert got == (256, 256)
    # What the probes traced is forgotten, so that the programs compiled
    # next get the persistent-cache keys of a process that never probed.
    assert cleared == [1]


def test_paged_tuner_raises_when_every_probe_fails(tmp_path, monkeypatch):
    """The paged tuner offers only block_h the lowering admits, and when
    the compiler refuses all of them it raises with that message instead
    of handing the engine a kernel that cannot run."""
    from determined_tpu.ops.paged_attention import paged_block_h_candidates

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class _Dev:
        device_kind = "fake"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    tried = []

    def refuse(block_h, **kw):
        tried.append(block_h)
        raise RuntimeError("Mosaic failed to compile TPU kernel: no")

    monkeypatch.setattr(fat, "_probe_paged_ms", refuse)
    cache = tmp_path / "cache.json"
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        fat.tune_paged_block_h(
            n_heads=12, head_dim=64, page_size=128, num_pages=129,
            pages_per_slot=8, batch=8, q_rows=8, cache_file=str(cache),
        )
    # 12 x 64: a block's minor extent is block_h * 64 lanes — a multiple
    # of 128, or all 768.
    assert tried == [2, 4, 6, 12]
    assert tried == paged_block_h_candidates(12, 64, 128, jnp.bfloat16)
    assert not cache.exists()


def test_autotune_cache_sits_in_the_checkout(monkeypatch):
    import os

    from determined_tpu.common import compile_cache

    monkeypatch.delenv("DTPU_FLASH_TUNE_CACHE", raising=False)
    assert fat.cache_path() == os.path.join(
        compile_cache.cache_root(), "flash_blocks.json"
    )
    monkeypatch.setenv("DTPU_FLASH_TUNE_CACHE", "/elsewhere/t.json")
    assert fat.cache_path() == "/elsewhere/t.json"


def test_segments_mode_probes_and_keys_separately(tmp_path, monkeypatch):
    """segments=True carries through to the probe (every candidate times
    the kernel a packed batch actually runs) and gets its own cache key —
    a segment-free winner is never applied to packed training."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    class _Dev:
        device_kind = "fake"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    seg_flags = []

    def fake_probe(bq, bk, **kw):
        seg_flags.append(kw.get("segments"))
        return float(bq)

    monkeypatch.setattr(fat, "_probe_ms", fake_probe)
    cache = tmp_path / "cache.json"
    fat.tune_flash_blocks(
        s_q=64, n_heads=2, head_dim=16, want_q=64, want_k=64,
        cache_file=str(cache),
    )
    assert seg_flags and all(f is False for f in seg_flags)
    seg_flags.clear()
    fat.tune_flash_blocks(
        s_q=64, n_heads=2, head_dim=16, want_q=64, want_k=64,
        segments=True, cache_file=str(cache),
    )
    assert seg_flags and all(f is True for f in seg_flags)
    data = json.loads(cache.read_text())
    assert len(data) == 2  # distinct keys
    assert any("seg1" in k for k in data) and any("seg0" in k for k in data)


def test_probe_with_segments_runs():
    """The segment-carrying probe executes end to end (CPU blockwise
    path): real fwd+bwd with segment operands, finite timing."""
    ms = fat._probe_ms(
        16, 16, s_q=64, s_k=64, n_heads=2, head_dim=16, batch=1,
        dtype=jnp.float32, causal=True, window=None, segments=True,
    )
    assert 0 < ms < float("inf")
