"""The plain reference of GLM-4.7-Flash (`model_type` `glm4_moe_lite`):
forward, loss (both terms) and, through `jax.grad`, its gradient in
float32 `jax.numpy`, and the comparison that decides `correct` in the
cells of this configuration.

Written from the equations the public `config.json`'s keys fix
(https://huggingface.co/zai-org/GLM-4.7-Flash; the DeepSeek-V3 block at
other numbers), h being a block's input after its norm:

- `RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w`; block
  `x += MLA(RMSNorm(x))`, `x += FFN_l(RMSNorm(x))`; a final RMSNorm; an
  untied head; no biases; no position embedding.
- MLA: `c_q = RMSNorm(h W_qa)`; `[q_nope | q_r] = c_q W_qb` a head;
  `[c_kv | k_r] = h W_kva`; `c_kv = RMSNorm(c_kv)`;
  `[k_nope | v] = c_kv W_kvb` a head. Rotary (rotate-half, `rope_theta`)
  on each head's `q_r` and on the one `k_r` a token, which every head
  uses. Head j: `softmax(([q_nope | q_r]_j . [k_nope_j | k_r]) /
  sqrt(192 + 64) + causal) v_j`; the heads side by side through `W_o`.
  Computed a block of queries at a time: 8192 x 8192 scores of 20 heads
  in float32 do not fit otherwise. Same arithmetic.
- Layers below `first_k_dense_replace`: `FFN = SwiGLU` of width
  `intermediate_size`. The others, the expert layer: `s = sigmoid(h W_r)`
  over all routed experts; the `num_experts_per_tok` with the largest
  `s + b` are chosen (b the selection bias: in the choice only);
  `w_e = routed_scaling_factor s_e / (sum of the chosen s + 1e-20)`
  (`norm_topk_prob`); `y = SwiGLU_shared(h) + sum over chosen AND held
  of w_e SwiGLU_e(h)`. The experts held are `n_routed_experts` from
  `first_expert` of `num_experts_routed`: what the others would add is
  left out, as on the chip that holds this share. Every held expert is
  applied to every token and masked (dense: no sort, no grouping).
- MTP module: `h'_i = [RMSNorm(Emb(t_{i+1}); w_e) | RMSNorm(x_i; w_h)]
  W_eh` for i < S - 1, x the trunk's last hidden state BEFORE the final
  norm; one more block (MLA + expert layer) over those S - 1 positions;
  the module's own final RMSNorm; the model's own head: the logits for
  `t_{i+2}`.
- Loss: `mean_i CE(logits_i, t_{i+1}) + mtp_loss_weight mean_i
  CE(mtp_logits_i, t_{i+2})` over the vocabulary held (S - 1 and S - 2
  targets a row). No z-loss and no balance loss (the program adds
  neither).

Departures from the public implementation, all shared with the program:
rotary pairs are (j, j + 32) of the 64 (the checkpoint interleaves them:
a column permutation of W_qb and W_kva); the selection bias is a
constant; `mtp_loss_weight` has no public key (the configuration file's
`assumed`); weights are random.

It reads the program's parameter tree (`Glm4MoeLite.init`'s layout) and
the configuration's keys, and nothing else of the program. Use under
`jax.default_matmul_precision("highest")` (`loss_and_gradient` sets it).

`correct` (`check`) holds the program to two numbers at the timed sizes,
on the first batch and the initial parameters: the first step's loss,
and the gradient of every parameter leaf, |g - g_ref|_2 / |g_ref|_2,
worst leaf. `operand_mantissa` is the control: the same equations with
the operands of every product rounded to 3 mantissa bits (float8 e4m3's,
the nearest format below the configuration's bf16 operands), everything
else float32 as before. `check` must refuse it (`PERF.md` has both
readings).

Under a gradient nothing of a layer outlives its backward but its input
(`jax.checkpoint` a mixer, an FFN, a block of queries, an expert's
part), and the device holds the parameters and their gradient a layer an
array (`unstack`).
"""
from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

#: |program loss - float32 reference loss| allowed on the first batch, at
#: the timed sizes: `benchmark/reference.py`'s limit for the other
#: training cells. The loss is ln(19 360) = 9.87 a term at
#: initialisation, the mean of 8191 tokens' errors, so bf16 operands with
#: float32 accumulation move it by 1e-4 to 1e-3 (`PERF.md`, PR 35: the
#: readings). It holds the head, both loss terms and the finiteness of
#: the forward; a precision is GRADIENT_TOLERANCE's to see.
LOSS_TOLERANCE = 0.02
#: |g - g_ref|_2 / |g_ref|_2 allowed in the worst parameter leaf (a leaf
#: is one entry of the program's parameter tree, all its layers
#: together; the MTP module's block has leaves of its own), first batch,
#: initial parameters, timed sizes. Between two readings on the chip
#: (`PERF.md`, PR 35; `benchmark/tests/glm_control_readings.json`): the
#: program's largest over its seeds, 0.245 (the MTP module's router: one
#: layer's 64 columns; 0.20-0.22 in the trunk's router, 0.14-0.17 in the
#: expert matrices, where bf16 activations also move a token's
#: fourth-best expert across the cut; 0.013-0.06 in every other leaf),
#: and the control's (`operand_mantissa=3`), 0.67-0.68 in the routers,
#: 0.49-0.51 in the expert matrices and 0.07-0.24 elsewhere. The limit
#: is the two worst readings' geometric middle: 1.6 times the program's
#: largest, and the control fails it in six leaves.
GRADIENT_TOLERANCE = 0.4
#: Queries a block of the attention.
QUERY_BLOCK = 1024


def rounded_operands(mantissa: int):
    """The control's `ein`: `jnp.einsum` of operands rounded to `mantissa`
    bits first (their values only: cotangents pass as they are). Every
    product below is an `ein(spec, a, b)`, `jnp.einsum` in the reference."""
    def ein(spec, a, b):
        a, b = (x + jax.lax.stop_gradient(jax.lax.reduce_precision(
            x, 8, mantissa) - x) for x in (a, b))
        return jnp.einsum(spec, a, b)

    return ein


def _rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _swiglu(h, w_in, w_out, ein):
    gate_up = ein("sd,dgf->sgf", h, w_in)
    return ein("sf,fd->sd", _silu(gate_up[:, 0]) * gate_up[:, 1], w_out)


def _rotary(x, theta):
    """x [S, ..., R]: rotate-half over the whole last axis, position =
    row."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (
        np.arange(half, dtype=np.float32) * 2 / x.shape[-1])
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    angle = angle.reshape(x.shape[0], *(1,) * (x.ndim - 2), half)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def latent_attention(h, w, cfg, ein=jnp.einsum):
    """h [S, D] of one sequence -> [S, D]."""
    s = h.shape[0]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    n_h = cfg["num_attention_heads"]
    c_q = _rmsnorm(ein("sd,dr->sr", h, w["wq_a"]), w["q_norm"], eps)
    q = ein("sr,rhk->shk", c_q, w["wq_b"])
    kv_a = ein("sd,dr->sr", h, w["wkv_a"])
    c_kv = _rmsnorm(kv_a[:, :rank], w["kv_norm"], eps)
    k_r = _rotary(kv_a[:, rank:], theta)                     # [S, R]: one a token
    kv = ein("sr,rhk->shk", c_kv, w["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_r = q[..., :nope], _rotary(q[..., nope:], theta)
    width = nope + cfg["qk_rope_head_dim"]
    block = min(QUERY_BLOCK, s)
    pad = -s % block        # padded query rows see every key; cut below

    def padded(x):
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))

    q_nope, q_r = padded(q_nope), padded(q_r)

    @jax.checkpoint
    def rows(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, block, 0)
        # a head's own part, plus the rotary part against the shared key
        scores = (ein("qhk,shk->hqs", qn, k_nope)
                  + ein("qhk,sk->hqs", qr, k_r)) / np.sqrt(width)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return ein("hqs,shk->qhk", jax.nn.softmax(scores, -1), v)

    o = jax.lax.map(rows, jnp.arange(0, s + pad, block)).reshape(
        s + pad, n_h, v.shape[-1])[:s]
    return ein("shk,hkd->sd", o, w["wo"])


def dense_ffn(h, w, cfg, ein=jnp.einsum):
    del cfg
    return _swiglu(h, w["w_in"], w["w_out"], ein)


def expert_layer(h, w, cfg, ein=jnp.einsum):
    """h [S, D] -> [S, D]: the held experts' part plus the shared expert."""
    top_k = cfg["num_experts_per_tok"]
    first = cfg.get("first_expert", 0)
    s = jax.nn.sigmoid(ein("sd,de->se", h, w["router"]))
    _, top_e = jax.lax.top_k(s + w["bias"], top_k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    top_s = top_s * cfg["routed_scaling_factor"]

    @jax.checkpoint   # (of no carry: a gradient keeps nothing an expert)
    def part(e, w_in, w_out):
        weight = jnp.sum(jnp.where(top_e == first + e, top_s, 0.0), -1)
        return weight[:, None] * _swiglu(h, w_in, w_out, ein)

    def one(y, expert):
        return y + part(*expert), None

    n_held = w["w_in"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(n_held), w["w_in"], w["w_out"]))
    return y + _swiglu(h, w["shared_in"], w["shared_out"], ein)


def _blocks(tree: Dict[str, Any], dense: Any) -> list:
    """A stack a kind -> a list of blocks, the dense ones first."""
    n_dense = 0 if dense is None else dense["w_in"].shape[0]
    out = []
    for i in range(tree["norm1"].shape[0]):
        ffn = (("dense", jax.tree.map(lambda a: a[i], dense)) if i < n_dense
               else ("moe", jax.tree.map(lambda a: a[i - n_dense],
                                         tree["moe"])))
        out.append({"norm1": tree["norm1"][i], "norm2": tree["norm2"][i],
                    "attn": jax.tree.map(lambda a: a[i], tree["attn"]),
                    ffn[0]: ffn[1]})
    return out


def unstack(params: Dict[str, Any]) -> Dict[str, Any]:
    """`Glm4MoeLite.init`'s tree with a block an entry of `layers` (there
    a kind's layers are one leaf, stacked). Views, if numpy's."""
    out = {"layers": _blocks(params, params.get("dense")),
           **{k: params[k] for k in ("tok_embed", "head", "norm_f")}}
    if "mtp" in params:
        mtp = params["mtp"]
        out["mtp"] = {"layers": _blocks(mtp, None), **{
            k: mtp[k] for k in ("enorm", "hnorm", "eh_proj", "norm_f")}}
    return out


def _block(x, layer, cfg, ein):
    eps = cfg["rms_norm_eps"]
    run = lambda f: jax.checkpoint(  # noqa: E731
        lambda h, w: f(h, w, cfg, ein))
    x = x + run(latent_attention)(
        _rmsnorm(x, layer["norm1"], eps), layer["attn"])
    kind, ffn = ("dense", dense_ffn) if "dense" in layer else (
        "moe", expert_layer)
    return x + run(ffn)(_rmsnorm(x, layer["norm2"], eps), layer[kind])


def forward(params: Dict[str, Any], tokens: jax.Array,
            cfg: Mapping[str, Any], ein=jnp.einsum):
    """tokens [B, S] -> (logits [B, S, V], the MTP module's logits
    [B, S - 1, V] or None), float32 throughout; `params` the program's
    tree or `unstack` of it."""
    if "layers" not in params:
        params = unstack(params)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps = cfg["rms_norm_eps"]

    def head(x, norm):
        return ein("sd,dv->sv", _rmsnorm(x, norm, eps), params["head"])

    @jax.checkpoint
    def sequence(row):
        x = params["tok_embed"][row]
        for layer in params["layers"]:
            x = _block(x, layer, cfg, ein)
        logits = head(x, params["norm_f"])
        if "mtp" not in params:
            return logits, None
        m = params["mtp"]
        following = params["tok_embed"][row[1:]]
        x = ein("sd,de->se", jnp.concatenate(
            [_rmsnorm(following, m["enorm"], eps),
             _rmsnorm(x[:-1], m["hnorm"], eps)], -1), m["eh_proj"])
        for layer in m["layers"]:
            x = _block(x, layer, cfg, ein)
        return logits, head(x, m["norm_f"])

    return jax.lax.map(sequence, tokens)


def _cross_entropy(logits, targets):
    lse = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - target)


def loss_terms(params, tokens, cfg, ein=jnp.einsum):
    """(next-token term, MTP term or None): each a mean over its targets."""
    logits, mtp_logits = forward(params, tokens, cfg, ein)
    main = _cross_entropy(logits[:, :-1], tokens[:, 1:])
    if mtp_logits is None:
        return main, None
    return main, _cross_entropy(mtp_logits[:, :-1], tokens[:, 2:])


def loss(params: Dict[str, Any], tokens: jax.Array,
         cfg: Mapping[str, Any], ein=jnp.einsum) -> jax.Array:
    main, mtp = loss_terms(params, tokens, cfg, ein)
    return main if mtp is None else main + cfg["mtp_loss_weight"] * mtp


def loss_and_gradient(params: Dict[str, Any], tokens: np.ndarray,
                      cfg: Mapping[str, Any], operand_mantissa=None):
    """(loss, its gradient `unstack`ed, numpy) of a whole batch, one row
    at a time inside. `params`: the program's tree on the HOST (numpy):
    the device gets it a layer an array and returns the gradient so.
    `operand_mantissa`: the control."""
    ein = (jnp.einsum if operand_mantissa is None
           else rounded_operands(operand_mantissa))
    fn = jax.jit(jax.value_and_grad(lambda p, t: loss(p, t, cfg, ein)))
    with jax.default_matmul_precision("highest"):
        value, grads = fn(unstack(params), jnp.asarray(tokens))
    return float(value), jax.device_get(grads)


def _leaf_name(path) -> str:
    """A leaf's name in the program's tree: `unstack`'s ("layers", index)
    pairs taken out of the path."""
    keys = [p for i, p in enumerate(path)
            if getattr(p, "key", None) != "layers"
            and not (i and getattr(path[i - 1], "key", None) == "layers")]
    return jax.tree_util.keystr(tuple(keys))


def gradient_gaps(got: Dict[str, Any], want: Dict[str, Any]
                  ) -> Dict[str, float]:
    """{leaf of the program's tree: |g - g_ref|_2 / |g_ref|_2 over all
    its layers}; `got` that tree or `unstack` of it, `want` `unstack`ed,
    both numpy. Where the reference's gradient is zero (the selection
    bias) the gap is 0 if the program's is zero too, else infinite."""
    squares: Dict[str, np.ndarray] = {}
    if "layers" not in got:
        got = unstack(got)
    pairs = zip(jax.tree_util.tree_leaves_with_path(got),
                jax.tree.leaves(want), strict=True)
    for (path, g), w in pairs:
        leaf = _leaf_name(path)
        g, w = (np.asarray(x, np.float32).ravel() for x in (g, w))
        squares[leaf] = squares.get(leaf, 0.0) + np.array(
            [np.dot(g - w, g - w), np.dot(w, w)], np.float64)

    def ratio(num, den):
        if den == 0.0:
            return 0.0 if num == 0.0 else float("inf")
        return float(np.sqrt(num / den))

    return {leaf: ratio(*sq) for leaf, sq in sorted(squares.items())}


def check(program_loss: float, reference_loss: float,
          gaps: Mapping[str, float]) -> Dict[str, Any]:
    """`correct`: the loss within LOSS_TOLERANCE and the worst leaf's
    gradient within GRADIENT_TOLERANCE; a gap that is not finite fails."""
    gap = abs(program_loss - reference_loss)
    worst = max(gaps, key=lambda k: (
        gaps[k] if np.isfinite(gaps[k]) else float("inf")))
    finite = all(np.isfinite(v) for v in gaps.values())
    return {
        "ok": bool(gap <= LOSS_TOLERANCE and finite
                   and gaps[worst] <= GRADIENT_TOLERANCE),
        "program": program_loss, "reference": reference_loss, "gap": gap,
        "tolerance": LOSS_TOLERANCE,
        "gradient_gap": gaps[worst], "gradient_gap_of": worst,
        "gradient_tolerance": GRADIENT_TOLERANCE,
        "gradient_gaps": {k: round(v, 6) for k, v in gaps.items()}}
