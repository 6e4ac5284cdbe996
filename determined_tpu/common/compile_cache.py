"""Where compiled programs and tuned kernel shapes are kept.

Every entry point that compiles for the device — the trial harness, the
serving service, the benchmark's drivers, `chip_smoke.py`'s children — calls
`enable()` once before its first jit. The directory, in order:

1. `JAX_COMPILATION_CACHE_DIR`: jax reads it by itself, so nothing is set
   in code and whoever runs the machine decides where entries land;
2. the caller's `override` (the trial harness passes the experiment's
   `environment.compilation_cache_dir`);
3. `<checkout>/.cache/xla` — fixed, because a cache that moves never
   hits, and inside the checkout (git-ignored), so two checkouts of two
   commits never hand each other compiled code or tuned blocks.

The flash/paged autotuner's winners (`ops/flash_autotune.py`) sit next
to it in `<checkout>/.cache/flash_blocks.json`.
"""
from __future__ import annotations

import os
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_root() -> str:
    """`<checkout>/.cache`: the one directory this package writes derived
    state to (compiled programs, autotuner winners)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".cache")


def cache_dir(override: Optional[str] = None) -> str:
    """The compile-cache directory by the order above (no jax needed)."""
    return (
        os.environ.get(ENV)
        or override
        or os.path.join(cache_root(), "xla")
    )


def enable(override: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache for this process and
    return the directory in use."""
    path = cache_dir(override)
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def entry_count(path: str) -> int:
    """Compiled programs in a cache directory (0 when it is absent)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except OSError:
        return 0
