"""Operations and bytes of a language model's train step, from the
configuration's public keys (`benchmark/configs/qwen3-next-*.json`):
what `flops_bytes.py` is to GPT-2. Nothing here reads the program.

Conventions, as in `flops_bytes.train_flops_per_token` (PaLM's): 6 per
matmul weight a token takes part in, forward and backward; attention's
two score-sized products over the whole square (not halved for the
causal mask); nothing recomputed counts. The routed experts are counted
by the rows actually routed to the experts held (`moe_held_rows_per_token`,
the program's counter: 10 when all experts are held, 0.625 for a
balanced sixteenth), not by the router's k.

The gated delta rule, by shape: a token and value head need S^T k, the
rank-one update k d^T and S^T q, 2 x Dk x Dv operations each forward
(the decay and the elementwise parts are left out), twice that backward.
The chunked form the program runs does other products (K K^T, the
triangular inverse); they are its choice and are not counted.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

BF16 = 2


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    layers, interval = c["num_hidden_layers"], c["full_attention_interval"]
    return {
        "d": c["hidden_size"], "layers": layers, "vocab": c["vocab_size"],
        "attn_layers": layers // interval,
        "gdn_layers": layers - layers // interval,
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "gdn_k_heads": c["linear_num_key_heads"],
        "gdn_v_heads": c["linear_num_value_heads"],
        "gdn_dk": c["linear_key_head_dim"],
        "gdn_dv": c["linear_value_head_dim"],
        "experts_held": c["num_experts"],
        "experts_routed": c.get("num_experts_routed") or c["num_experts"],
        "top_k": c["num_experts_per_tok"],
        "expert_width": c["moe_intermediate_size"],
        "shared_width": c["shared_expert_intermediate_size"],
    }


def matmul_params_per_token(c: Dict[str, Any],
                            held_rows_per_token: float) -> float:
    """Weights a token is multiplied by, all layers and the head."""
    m = dims(c)
    d = m["d"]
    kd, vd = m["gdn_k_heads"] * m["gdn_dk"], m["gdn_v_heads"] * m["gdn_dv"]
    gdn = d * (2 * kd + 2 * vd) + d * 2 * m["gdn_v_heads"] + vd * d
    attn = (d * m["heads"] * 2 * m["head_dim"]
            + d * 2 * m["kv_heads"] * m["head_dim"]
            + m["heads"] * m["head_dim"] * d)
    moe = (d * m["experts_routed"] + d + 3 * d * m["shared_width"]
           + held_rows_per_token * 3 * d * m["expert_width"])
    return (m["gdn_layers"] * gdn + m["attn_layers"] * attn
            + m["layers"] * moe + d * m["vocab"])


def delta_rule_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward operations of the rule itself, one layer, all value heads."""
    m = dims(c)
    return 6.0 * m["gdn_v_heads"] * m["gdn_dk"] * m["gdn_dv"]


def train_flops_per_token(c: Dict[str, Any], seq_len: int,
                          held_rows_per_token: float) -> float:
    m = dims(c)
    return (6.0 * matmul_params_per_token(c, held_rows_per_token)
            + 12.0 * m["attn_layers"] * m["heads"] * m["head_dim"] * seq_len
            + 3.0 * m["gdn_layers"] * delta_rule_flops_per_token(c))


def delta_rule_step(c: Dict[str, Any], tokens: int) -> Tuple[float, float]:
    """(operations, bytes) of the rule in one train step, all delta
    layers, forward and backward: forward reads q, k (key heads), v, g,
    beta and writes o; backward reads those and do and writes their
    gradients (bf16; g and beta float32)."""
    m = dims(c)
    flops = 3.0 * m["gdn_layers"] * tokens * delta_rule_flops_per_token(c)
    qk = 2 * m["gdn_k_heads"] * m["gdn_dk"]
    v = m["gdn_v_heads"] * m["gdn_dv"]
    scalars = 2 * m["gdn_v_heads"] * 4
    fwd = BF16 * (qk + 2 * v) + scalars
    bwd = BF16 * (2 * qk + 3 * v) + 2 * scalars
    return flops, float(m["gdn_layers"] * tokens * (fwd + bwd))


def experts_step(c: Dict[str, Any], tokens: int,
                 held_rows_per_token: float) -> Tuple[float, float]:
    """(operations, bytes) of the grouped matmuls in one train step, all
    layers, for the rows actually routed: 3 matrices of D x F an expert,
    6 operations a weight and row (forward, dX, dW). Bytes: each held
    expert's weights read forward and backward and their gradient
    written (bf16), and a row's input, two hidden activations and output
    read or written once forward and twice backward."""
    m = dims(c)
    d, f = m["d"], m["expert_width"]
    rows = held_rows_per_token * tokens
    flops = m["layers"] * rows * 6.0 * 3 * d * f
    weights = m["experts_held"] * 3 * d * f * BF16 * 3
    acts = rows * (2 * d + 3 * f) * BF16 * 3
    return flops, float(m["layers"] * (weights + acts))
