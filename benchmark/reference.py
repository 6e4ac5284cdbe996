"""The plain reference: GPT-2's forward pass and loss in float32
`jax.numpy`, and the comparisons that decide `correct`.

Written from the published description (Radford et al. 2019; the
`GPT2Model` equations): learned token and position embeddings,
pre-LayerNorm blocks (eps 1e-5) of causal multi-head attention with
scores scaled by 1/sqrt(head_dim) and a GELU (tanh form, `gelu_new`) MLP,
a final LayerNorm, and the head tied to the token embedding. No kernel,
no cache, no batching tricks, no bf16; `jax.default_matmul_precision
("highest")` around every use, because a TPU otherwise runs a float32
matmul in bf16 passes. The loss adds the program's z-loss (1e-4 x
mean(logsumexp^2)), a departure from the paper that `models/gpt.py`
makes and the reference follows so that the two losses are comparable.

It reads the program's parameter tree (`GPT.init`'s layout: `wqkv`
[L, D, 3, H, Dh], `wo` [L, H, Dh, D], ...) and nothing else of the
program. Tolerances are `chip_smoke.py`'s (PR 21), with their reasons.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: |program loss - float32 reference loss| allowed on one batch. The loss
#: is ~11 (ln 50304 plus the z-loss); bf16 carries 8 bits of mantissa and
#: the errors of 16k tokens average out: chip_smoke's first chip run saw
#: 5e-5 to 1.5e-3 (PR 21). Ten times the worst. Computing the whole
#: forward in bf16 *accumulation* (not only bf16 inputs) moves it by more.
LOSS_TOLERANCE = 0.02
#: A served greedy token may differ from the reference's argmax only
#: where the reference itself scores the two within this logit gap: with
#: random weights the top logits of 50304 sit ~0.1 apart, and bf16
#: activations move a logit by about a hundredth (PR 21).
LOGIT_MARGIN = 0.05
Z_LOSS = 1e-4


def _layernorm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def forward(params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V], float32 throughout."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    s = tokens.shape[1]
    x = f32(params["tok_embed"])[tokens] + f32(params["pos_embed"])[:s]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, blk):
        blk = jax.tree.map(f32, blk)
        h = _layernorm(x, blk["ln1_scale"], blk["ln1_bias"])
        qkv = jnp.einsum("bsd,dthk->tbhsk", h, blk["wqkv"])
        qkv = qkv + blk["bqkv"][:, None, :, None, :]
        q, k, v = qkv[0], qkv[1], qkv[2]
        scores = jnp.einsum("bhqk,bhsk->bhqs", q, k) / np.sqrt(q.shape[-1])
        scores = jnp.where(causal, scores, -jnp.inf)
        o = jnp.einsum("bhqs,bhsk->bqhk", jax.nn.softmax(scores, -1), v)
        x = x + jnp.einsum("bqhk,hkd->bqd", o, blk["wo"]) + blk["bo"]
        h = _layernorm(x, blk["ln2_scale"], blk["ln2_bias"])
        m = _gelu_new(jnp.einsum("bsd,df->bsf", h, blk["wi"]) + blk["bi"])
        x = x + jnp.einsum("bsf,fd->bsd", m, blk["wo_mlp"]) + blk["bo_mlp"]
        return x, None

    # scan: one compiled block whatever the depth (48 unrolled float32
    # blocks take minutes to compile); the arithmetic is the loop's.
    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _layernorm(x, f32(params["lnf_scale"]), f32(params["lnf_bias"]))
    return jnp.einsum("bsd,vd->bsv", x, f32(params["tok_embed"]))


def loss(params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy plus the z-loss, over [B, S] tokens."""
    logits = forward(params, tokens)[:, :-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - target) + Z_LOSS * jnp.mean(jnp.square(lse))


def batch_loss(params: Dict[str, Any], tokens: np.ndarray,
               rows_per_call: int) -> float:
    """The reference loss of a whole batch, `rows_per_call` rows at a
    time (equal slices, so the mean of their means is the batch's):
    float32 dense attention over a training batch does not fit a chip."""
    if tokens.shape[0] % rows_per_call:
        raise ValueError(
            f"{tokens.shape[0]} rows do not divide into {rows_per_call}s")
    fn = jax.jit(loss)
    with jax.default_matmul_precision("highest"):
        parts = [
            float(fn(params, jnp.asarray(tokens[i:i + rows_per_call])))
            for i in range(0, tokens.shape[0], rows_per_call)
        ]
    return float(np.mean(parts))


def check_loss(program_loss: float, reference_loss: float) -> Dict[str, Any]:
    gap = abs(program_loss - reference_loss)
    return {"ok": bool(gap <= LOSS_TOLERANCE), "program": program_loss,
            "reference": reference_loss, "gap": gap,
            "tolerance": LOSS_TOLERANCE}


def check_greedy(params: Dict[str, Any],
                 served: Sequence[Dict[str, List[int]]],
                 pad_to: int) -> Dict[str, Any]:
    """Teacher-forced check of served greedy tokens: one float32 forward
    over prompt + generated (padded to `pad_to`; causal, so padding never
    reaches a compared position); position i must arg-max-predict token
    i + 1, or score it within LOGIT_MARGIN of the arg-max (a bf16
    near-tie). `served`: [{"prompt": [...], "tokens": [...]}]."""
    fn = jax.jit(forward)
    exact = near = 0
    worst = 0.0
    misses: List[str] = []
    with jax.default_matmul_precision("highest"):
        for n, got in enumerate(served):
            seq = list(got["prompt"]) + list(got["tokens"])
            if len(seq) > pad_to:
                raise ValueError(f"sequence of {len(seq)} > pad_to {pad_to}")
            padded = np.zeros((1, pad_to), np.int32)
            padded[0, :len(seq)] = seq
            logits = np.asarray(fn(params, jnp.asarray(padded))[0])
            for i in range(len(got["prompt"]) - 1, len(seq) - 1):
                row, tok = logits[i], seq[i + 1]
                gap = float(row.max() - row[tok])
                if gap == 0.0:
                    exact += 1
                elif gap <= LOGIT_MARGIN:
                    near += 1
                    worst = max(worst, gap)
                else:
                    misses.append(
                        f"request {n} token {i + 1 - len(got['prompt'])}: "
                        f"served {tok}, reference {int(row.argmax())}, "
                        f"gap {gap:.4f}")
    return {"ok": bool(not misses and exact > 0), "exact": exact,
            "near_tie": near, "worst_near_tie_gap": worst,
            "misses": misses[:5], "margin": LOGIT_MARGIN}
