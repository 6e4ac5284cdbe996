"""Pallas TPU kernels + blockwise reference paths for the hot ops."""
from determined_tpu.ops.flash_attention import (
    block_skip_stats,
    fit_block,
    flash_attention,
    flash_attention_lse,
    flash_attention_qkv,
)
from determined_tpu.ops.paged_attention import paged_attention

__all__ = [
    "block_skip_stats",
    "fit_block",
    "flash_attention",
    "flash_attention_lse",
    "flash_attention_qkv",
    "paged_attention",
]
