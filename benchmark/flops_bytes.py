"""Operations and bytes a call needs, from its shapes alone.

The yardstick's arithmetic: nothing here reads the program, a trace or a
compiler's count, so a PR that changes the program cannot change what a
utilization or a roofline share is measured against. Recomputed
operations (remat, the flash backward's second pass over the scores)
never count: these are the operations the algorithm needs.

A configuration is the dict of `benchmark/configs/<name>.json` (the
public config.json's keys). Rooflines: the least time a call can take is
the larger of operations over peak FLOP/s and bytes over peak bytes/s
(`roofline_seconds`), and a kernel's roofline share is that over its
measured time.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

BF16 = 2
F32 = 4


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    d = int(config["n_embd"])
    return {
        "layers": int(config["n_layer"]),
        "heads": int(config["n_head"]),
        "d_model": d,
        "head_dim": d // int(config["n_head"]),
        "d_ff": int(config["n_inner"] or 4 * d),
        "vocab": int(config.get("padded_vocab_size") or config["vocab_size"]),
        "positions": int(config["n_positions"]),
    }


def matmul_params(config: Dict[str, Any]) -> int:
    """Weights that take part in a matrix multiplication per token: the
    four attention projections and the two MLP matrices of every block,
    and the (tied) output head. Embedding look-ups are gathers."""
    m = dims(config)
    d, f = m["d_model"], m["d_ff"]
    return m["layers"] * (4 * d * d + 2 * d * f) + d * m["vocab"]


def n_params(config: Dict[str, Any]) -> int:
    """All parameters (tied head counted once), biases and norms included."""
    m = dims(config)
    d, f = m["d_model"], m["d_ff"]
    per_block = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
    return (m["layers"] * per_block + m["vocab"] * d + m["positions"] * d
            + 2 * d)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations per trained token, PaLM's
    convention (copied from `GPTConfig.train_flops_per_token`): 6 per
    matmul weight, plus 12 x layers x d_model x seq_len for attention's
    two score-sized products (the whole square: the convention does not
    halve for the causal mask, so an MFU from it is comparable with the
    published ones)."""
    m = dims(config)
    return (6.0 * matmul_params(config)
            + 12.0 * m["layers"] * m["d_model"] * seq_len)


def _attn_io_bytes(batch: int, heads: int, seq_q: int, seq_k: int,
                   head_dim: int, n_q_like: int, n_k_like: int) -> float:
    """bf16 tensors of q's shape and of k's shape, each read or written
    once, plus one fp32 row statistic per query (the log-sum-exp)."""
    return (BF16 * batch * heads * head_dim
            * (n_q_like * seq_q + n_k_like * seq_k)
            + F32 * batch * heads * seq_q)


def flash_forward(batch: int, heads: int, seq_q: int, seq_k: int,
                  head_dim: int, causal: bool = True) -> Tuple[float, float]:
    """(operations, bytes) of one attention forward: QK^T and PV, 2 x 2
    x S_q x S_k x D each head, halved under the causal mask (the kernel
    skips blocks above the diagonal); reads q, k, v, writes o and lse."""
    flops = 4.0 * batch * heads * seq_q * seq_k * head_dim
    if causal:
        flops *= 0.5
    return flops, _attn_io_bytes(batch, heads, seq_q, seq_k, head_dim, 2, 2)


def flash_backward(batch: int, heads: int, seq_q: int, seq_k: int,
                   head_dim: int, causal: bool = True) -> Tuple[float, float]:
    """(operations, bytes) of one attention backward: dV = P^T dO, dP =
    dO V^T, dQ = dS K, dK = dS^T Q: four score-sized products. The
    recomputation of the scores (a fifth) is the kernel's choice, not the
    algorithm's need, and is not counted. Reads q, k, v, o, dO, lse;
    writes dq, dk, dv."""
    flops = 8.0 * batch * heads * seq_q * seq_k * head_dim
    if causal:
        flops *= 0.5
    return flops, _attn_io_bytes(batch, heads, seq_q, seq_k, head_dim, 4, 4)


def paged_decode(cached_tokens: float, pages_read: float, page_size: int,
                 heads: int, head_dim: int) -> Tuple[float, float]:
    """(operations, bytes) of decode attention in ONE layer over a batch
    whose slots hold `cached_tokens` keys in all and touch `pages_read`
    pool pages: one query row a slot (padding rows are the kernel's
    choice), QK^T and PV over the live keys; every touched page of K and
    of V is read once in bf16 (q, o and the page table are noise)."""
    flops = 4.0 * cached_tokens * heads * head_dim
    nbytes = 2.0 * pages_read * page_size * heads * head_dim * BF16
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Dict[str, float]) -> Tuple[float, str]:
    """Least time on one chip, and which peak bounds it."""
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    if t_flops >= t_bytes:
        return t_flops, "compute"
    return t_bytes, "memory"
