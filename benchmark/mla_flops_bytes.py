"""Operations and bytes of a latent-attention (MLA) mixture-of-experts
model's train step, from the configuration's public keys
(`benchmark/configs/glm-4.7-flash.json`): what `lm_flops_bytes.py` is to
Qwen3-Next (its `dims` reads keys this family does not have). Nothing
here reads the program.

Conventions, as in `flops_bytes.train_flops_per_token` (PaLM's): 6 per
matmul weight a token takes part in, forward and backward; attention's
two score-sized products over the whole square (not halved for the
causal mask); nothing recomputed counts. The routed experts are counted
by the rows actually routed to the experts held
(`moe_held_rows_per_token`, the program's counter: 4 when all 64 are
held, 0.5 for a balanced eighth), not by the router's k.

The multi-token-prediction module (`num_nextn_predict_layers` 1) is one
more block (MLA + expert layer), its 2D x D input projection and a
second pass through the head; the counter is the mean over all expert
layers, the module's included.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

BF16 = 2


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    layers, mtp = c["num_hidden_layers"], c.get("num_nextn_predict_layers", 0)
    dense = c["first_k_dense_replace"]
    return {
        "d": c["hidden_size"], "vocab": c["vocab_size"], "mtp": mtp,
        "attn_layers": layers + mtp, "dense_layers": dense,
        "expert_layers": layers - dense + mtp,
        "heads": c["num_attention_heads"],
        "qk_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
        "v_dim": c["v_head_dim"],
        "q_rank": c["q_lora_rank"], "kv_rank": c["kv_lora_rank"],
        "dense_width": c["intermediate_size"],
        "experts_held": c["n_routed_experts"],
        "experts_routed": c.get("num_experts_routed") or c["n_routed_experts"],
        "top_k": c["num_experts_per_tok"],
        "expert_width": c["moe_intermediate_size"],
        "shared_width": c["n_shared_experts"] * c["moe_intermediate_size"],
    }


def mla_params(c: Dict[str, Any]) -> int:
    """Matmul weights of one MLA mixer: the two query projections, the
    two key/value projections, the output projection."""
    m = dims(c)
    d, h = m["d"], m["heads"]
    return (d * m["q_rank"] + m["q_rank"] * h * m["qk_dim"]
            + d * (m["kv_rank"] + m["rope"])
            + m["kv_rank"] * h * (m["nope"] + m["v_dim"])
            + h * m["v_dim"] * d)


def matmul_params_per_token(c: Dict[str, Any],
                            held_rows_per_token: float) -> float:
    """Weights a token is multiplied by: all blocks, the MTP module's
    projection, and the head once a pass."""
    m = dims(c)
    d = m["d"]
    dense = 3 * d * m["dense_width"]
    moe = (d * m["experts_routed"] + 3 * d * m["shared_width"]
           + held_rows_per_token * 3 * d * m["expert_width"])
    return (m["attn_layers"] * mla_params(c) + m["dense_layers"] * dense
            + m["expert_layers"] * moe
            + m["mtp"] * 2 * d * d + (1 + m["mtp"]) * d * m["vocab"])


def train_flops_per_token(c: Dict[str, Any], seq_len: int,
                          held_rows_per_token: float) -> float:
    m = dims(c)
    return (6.0 * matmul_params_per_token(c, held_rows_per_token)
            + 12.0 * m["attn_layers"] * m["heads"] * m["qk_dim"] * seq_len)


def experts_step(c: Dict[str, Any], tokens: int,
                 held_rows_per_token: float) -> Tuple[float, float]:
    """(operations, bytes) of the grouped matmuls in one train step, all
    expert layers, for the rows actually routed: 3 matrices of D x F an
    expert, 6 operations a weight and row (forward, dX, dW). Bytes: each
    held expert's weights read forward and backward and their gradient
    written (bf16), and a row's input, two hidden activations and output
    read or written once forward and twice backward
    (`lm_flops_bytes.experts_step`'s count)."""
    m = dims(c)
    d, f = m["d"], m["expert_width"]
    rows = held_rows_per_token * tokens
    flops = m["expert_layers"] * rows * 6.0 * 3 * d * f
    weights = m["experts_held"] * 3 * d * f * BF16 * 3
    acts = rows * (2 * d + 3 * f) * BF16 * 3
    return flops, float(m["expert_layers"] * (weights + acts))
