"""Flash-attention block-size autotuner.

`GPTConfig.flash_block_q/k = 1024` was measured best for the GPT-2 bench on
a v5e — but one pair of constants cannot be right across v5e/v5p (different
VMEM/HBM ratios), sequence lengths (the 32k regime wants different tiles
than 1k) and masking modes (a sliding window changes the live-block
geometry). This module replaces the constant with a measurement: time the
real kernels (fwd + bwd, jitted) over a small candidate set at the exact
shapes/dtype the model will run, pick the fastest, and remember the answer
in a persistent on-disk cache so every later process (and every later bench
round) pays nothing.

Probing executes real device work, so it MUST run outside jit — callers
resolve block sizes at model-build time (see GPT._flash_blocks) and pass
plain ints into the traced code.

Cache: one JSON object at `DTPU_FLASH_TUNE_CACHE` (default
`<checkout>/.cache/flash_blocks.json`, next to the XLA compile cache —
common/compile_cache.py — so the winners that decide which kernel shape
runs live and die with the checkout), keyed by cache-format version,
device kind, jax version, folded shape, dtype and masking mode — any of
those changing invalidates the entry by construction; delete the file to
force a re-probe. Writes are atomic (tempfile + rename) and best-effort:
a read-only filesystem degrades to probing once per process.

A candidate the compiler refuses (VMEM, shape) loses and is named at
`warning`; when EVERY candidate fails the tuner raises with the
compiler's message — on a machine that has the chip there is no
untuned fallback to hide behind.

Off-TPU (CPU tests, trial processes on the master) no probe ever runs: the
tuner returns the caller's wanted blocks fitted to the sequence, which is
exactly the pre-autotuner behavior. `DTPU_FLASH_AUTOTUNE=0` forces that
everywhere.
"""
from __future__ import annotations

import json
import logging
import os
import tempfile
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from determined_tpu.common.compile_cache import cache_root
from determined_tpu.ops.flash_attention import (
    _MONO_MAX_SCORES,
    fit_block,
    flash_attention,
)

logger = logging.getLogger("determined_tpu.ops.flash_autotune")

#: Bump when the key schema or probe methodology changes incompatibly.
CACHE_VERSION = 1

#: (block_q, block_k) seeds; each is fitted to the actual sequence lengths
#: and deduped, and the monolithic single-block candidate joins the set
#: when it fits VMEM — so "mono vs blocked" is decided by the same timing
#: probe as the tile size, not by a separate hand-tuned threshold.
_CANDIDATE_SEEDS: Tuple[Tuple[int, int], ...] = (
    (256, 256),
    (512, 512),
    (1024, 1024),
    (2048, 1024),
    (1024, 512),
    (512, 1024),
)

#: Probe cost guardrails: per-candidate timed steps.
_PROBE_WARMUP = 1
_PROBE_STEPS = 3


def cache_path() -> str:
    return os.environ.get(
        "DTPU_FLASH_TUNE_CACHE",
        os.path.join(cache_root(), "flash_blocks.json"),
    )


def _load_cache(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except Exception:  # noqa: BLE001 - missing/corrupt cache: re-probe
        return {}


def _store_cache(path: str, data: dict) -> None:
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path), prefix=".flash_blocks."
        )
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(data, f, indent=0, sort_keys=True)
        os.replace(tmp, path)  # atomic: readers never see a torn file
    except Exception:  # noqa: BLE001 - cache is an optimization only
        logger.debug("flash autotune cache write failed", exc_info=True)


def _cache_key(device_kind: str, s_q: int, s_k: int, n_heads: int,
               head_dim: int, batch: int, dtype, causal: bool,
               window: Optional[int], segments: bool) -> str:
    return "|".join([
        f"v{CACHE_VERSION}",
        device_kind,
        f"jax{jax.__version__}",
        f"b{batch}h{n_heads}q{s_q}k{s_k}d{head_dim}",
        jnp.dtype(dtype).name,
        f"causal{int(causal)}",
        f"win{window if window is not None else 0}",
        f"seg{int(segments)}",
    ])


def candidate_blocks(s_q: int, s_k: int,
                     want_q: int = 1024, want_k: int = 1024
                     ) -> List[Tuple[int, int]]:
    """Fitted, deduped candidate list for one shape. The caller's wanted
    pair goes first (it wins ties and is the no-probe fallback); the
    (s_q, s_k) single-block candidate joins when the score matrix fits
    the mono VMEM budget. Which kernel a candidate times is decided by
    the probe's mask mode — under `segments` the single-block candidate
    exercises the BLOCKED kernel at block == seq (mono declines segment
    masking), which is faithfully what that configuration runs."""
    out: List[Tuple[int, int]] = []
    seeds = ((want_q, want_k),) + _CANDIDATE_SEEDS
    for bq, bk in seeds:
        cand = (fit_block(s_q, bq), fit_block(s_k, bk))
        if cand not in out:
            out.append(cand)
    if s_q * s_k <= _MONO_MAX_SCORES and (s_q, s_k) not in out:
        out.append((s_q, s_k))
    return out


def _best_of_ms(step: Callable[[], object]) -> float:
    """Best-of-N wall ms of `step` after warmup."""
    for _ in range(_PROBE_WARMUP):
        jax.block_until_ready(step())
    best = float("inf")
    for _ in range(_PROBE_STEPS):
        t0 = time.perf_counter()
        jax.block_until_ready(step())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _probe_ms(bq: int, bk: int, *, s_q: int, s_k: int, n_heads: int,
              head_dim: int, batch: int, dtype, causal: bool,
              window: Optional[int], segments: bool = False) -> float:
    """Best-of-N wall ms of one jitted fwd+bwd step at (bq, bk). Raises
    what the compiler raises (`_pick_fastest` decides what that means)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (batch, s_q, n_heads, head_dim), dtype)
    k = jax.random.normal(keys[1], (batch, s_k, n_heads, head_dim), dtype)
    v = jax.random.normal(keys[2], (batch, s_k, n_heads, head_dim), dtype)
    seg = kv_seg = None
    if segments:
        # Representative packed pattern: a few contiguous docs per
        # row. The mask VALUES barely matter for timing; the extra
        # operands and the segment-compare VPU work do.
        def runs(s):
            return jnp.cumsum(
                (jnp.arange(s) % max(s // 4, 1) == 0).astype(jnp.int32)
            )[None, :].repeat(batch, axis=0)

        seg, kv_seg = runs(s_q), runs(s_k)

    def loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, window=window, segment_ids=seg,
            kv_segment_ids=kv_seg, block_q=bq, block_k=bk,
        )
        return jnp.sum(o.astype(jnp.float32))

    # All three gradients: grad-wrt-q alone would let XLA dead-code the
    # dk/dv pass out of the two-pass backward split and rank candidates
    # on a backward real training never runs.
    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return _best_of_ms(lambda: step(q, k, v))


def _probe_paged_ms(block_h: int, *, n_heads: int, head_dim: int,
                    page_size: int, num_pages: int, pages_per_slot: int,
                    batch: int, q_rows: int, dtype) -> float:
    """Best-of-N wall ms of one jitted paged-attention decode step at
    `block_h` heads per grid step. Raises what the compiler raises."""
    import functools

    from determined_tpu.ops.paged_attention import paged_attention

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    # Probe on a REDUCED pool: per-step cost depends on the pages a
    # slot actually reads (page_size × pages_per_slot × batch), not
    # on total pool residency — and the engine calls this AFTER its
    # real pools are allocated, so probing at the full num_pages
    # would double peak HBM (and OOM exactly the headroom-sized
    # pools the tuner matters for).
    probe_pages = min(num_pages, batch * pages_per_slot + 1)
    kp = jax.random.normal(
        keys[0], (probe_pages, page_size, n_heads, head_dim), dtype
    )
    vp = jax.random.normal(
        keys[1], (probe_pages, page_size, n_heads, head_dim), dtype
    )
    q = jax.random.normal(
        keys[2], (batch, q_rows, n_heads, head_dim), dtype
    )
    # High-occupancy state: the regime the kernel exists for.
    pt = (
        jnp.arange(batch * pages_per_slot, dtype=jnp.int32)
        % max(probe_pages - 1, 1) + 1
    ).reshape(batch, pages_per_slot)
    lengths = jnp.full((batch,), pages_per_slot * page_size - 1,
                       jnp.int32)
    active = jnp.ones((batch,), jnp.int32)
    step = jax.jit(functools.partial(paged_attention, block_h=block_h))
    return _best_of_ms(lambda: step(q, kp, vp, pt, lengths, active))


def _pick_fastest(key: str, cands: Sequence, probe: Callable):
    """The fastest of `cands`. A candidate whose probe raises (the
    compiler refused its VMEM or shape) loses and is named at warning.
    Every candidate failing is an error carrying the compiler's message:
    there is no kernel to run, and an untuned guess would only move the
    same failure into the first training or decode step."""
    timings, last_err = {}, None
    for cand in cands:
        try:
            timings[cand] = probe(cand)
        except Exception as e:  # noqa: BLE001 — a losing candidate
            logger.warning("autotune %s: candidate %s failed: %s",
                           key, cand, str(e)[:500])
            last_err = e
    # Forget what the probes traced and compiled. The losers' executables
    # are dead weight, and — the reason it matters — a Mosaic kernel
    # serialises source locations that jax's tracing caches carry over
    # from whichever kernel was traced first: without this, the process
    # that probed compiles its real programs under other persistent-cache
    # keys than the next process, which reads the winner from disk, and
    # every first restart recompiles them.
    jax.clear_caches()
    if not timings:
        raise RuntimeError(
            f"autotune {key}: all {len(cands)} candidates failed; "
            f"last error: {last_err}"
        ) from last_err
    best = min(timings, key=timings.get)
    logger.info("autotune %s -> %s (%.2f ms; %d candidates)",
                key, best, timings[best], len(cands))
    return best


def tune_paged_block_h(
    *,
    n_heads: int,
    head_dim: int,
    page_size: int,
    num_pages: int,
    pages_per_slot: int,
    batch: int,
    q_rows: int = 1,
    dtype=jnp.bfloat16,
    cache_file: Optional[str] = None,
) -> int:
    """Resolve `block_h` (heads per grid step) for the paged decode
    kernel — the paged analog of `tune_flash_blocks`. The kernel's K
    block is pinned to one pool page, so the head grouping is the live
    tile knob: more heads per step amortize each page's DMA across heads
    at the cost of VMEM residency.

    Call OUTSIDE jit. Off-TPU (or with DTPU_FLASH_AUTOTUNE=0) returns
    the deterministic `default_paged_block_h`; on TPU the winner is
    probed once and cached, keyed by the FULL pool geometry (page_size ×
    num_pages × pages_per_slot × batch × heads/dim/q_rows/dtype) — a
    resized pool re-probes by construction. Raises if the compiler
    refuses every candidate.
    """
    from determined_tpu.ops.paged_attention import (
        default_paged_block_h,
        paged_block_h_candidates,
    )

    if (
        os.environ.get("DTPU_FLASH_AUTOTUNE", "1") == "0"
        or jax.default_backend() != "tpu"
    ):
        return default_paged_block_h(n_heads, head_dim, page_size, dtype)

    path = cache_file or cache_path()
    key = "|".join([
        f"v{CACHE_VERSION}",
        "paged",
        jax.devices()[0].device_kind,
        f"jax{jax.__version__}",
        f"b{batch}h{n_heads}d{head_dim}q{q_rows}",
        f"ps{page_size}np{num_pages}pp{pages_per_slot}",
        jnp.dtype(dtype).name,
    ])
    hit = _load_cache(path).get(key)
    if isinstance(hit, int) and hit >= 1:
        return hit
    # Only what the lowering admits and the VMEM budget fits
    # (paged_block_h_candidates): a refused candidate costs a full Pallas
    # compile just to lose.
    best = _pick_fastest(
        key,
        paged_block_h_candidates(n_heads, head_dim, page_size, dtype),
        lambda h: _probe_paged_ms(
            h, n_heads=n_heads, head_dim=head_dim, page_size=page_size,
            num_pages=num_pages, pages_per_slot=pages_per_slot,
            batch=batch, q_rows=q_rows, dtype=dtype,
        ),
    )
    cache = _load_cache(path)  # re-read: another process may have written
    cache[key] = int(best)
    _store_cache(path, cache)
    return best


def tune_flash_blocks(
    *,
    s_q: int,
    s_k: Optional[int] = None,
    n_heads: int,
    head_dim: int,
    batch: int = 1,
    dtype=jnp.bfloat16,
    causal: bool = True,
    window: Optional[int] = None,
    segments: bool = False,
    want_q: int = 1024,
    want_k: int = 1024,
    cache_file: Optional[str] = None,
) -> Tuple[int, int]:
    """Resolve (block_q, block_k) for one attention shape.

    Call OUTSIDE jit (this may execute probe steps on the device). Returns
    the fitted wanted blocks immediately off-TPU or when disabled via
    DTPU_FLASH_AUTOTUNE=0; otherwise returns the cached winner, probing
    once per (device kind, jax version, shape, dtype, mask mode). Raises
    if the compiler refuses every candidate.

    `segments`: tune for packed-sequence batches — the probe carries
    segment ids (so every candidate times the kernel that configuration
    actually runs; mono declines segments and its block==seq candidate
    falls through to the blocked kernel, in probe and production alike)
    and the cached entry is keyed separately from the segment-free one.
    """
    s_k = s_q if s_k is None else s_k
    fallback = (fit_block(s_q, want_q), fit_block(s_k, want_k))
    if os.environ.get("DTPU_FLASH_AUTOTUNE", "1") == "0":
        return fallback
    if jax.default_backend() != "tpu":
        return fallback

    path = cache_file or cache_path()
    key = _cache_key(
        jax.devices()[0].device_kind, s_q, s_k, n_heads, head_dim, batch,
        dtype, causal, window, segments,
    )
    cache = _load_cache(path)
    hit = cache.get(key)
    if isinstance(hit, (list, tuple)) and len(hit) == 2:
        return int(hit[0]), int(hit[1])

    best = _pick_fastest(
        key,
        candidate_blocks(s_q, s_k, want_q, want_k),
        lambda c: _probe_ms(
            c[0], c[1], s_q=s_q, s_k=s_k, n_heads=n_heads,
            head_dim=head_dim, batch=batch, dtype=dtype, causal=causal,
            window=window, segments=segments,
        ),
    )
    cache = _load_cache(path)  # re-read: another process may have written
    cache[key] = list(best)
    _store_cache(path, cache)
    return best
