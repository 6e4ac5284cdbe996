"""The plain reference of Qwen3-Next: forward, loss and (through
`jax.grad`) its gradient in float32 `jax.numpy`, and the comparison
that decides `correct` in the cells of this configuration.

Written from the equations (the model card and `modeling_qwen3_next.py`
of https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), h being a
block's input after its norm:

- `RMSNorm0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`; block
  `x += Mixer_l(RMSNorm0(x))`, `x += MoE(RMSNorm0(x))`; final RMSNorm0;
  untied head; no position embedding; of every `full_attention_interval`
  layers the last is gated attention and the others gated delta.
- Gated attention: `[q | gate] = h W_q` a head; `k, v` on
  `num_key_value_heads` heads, each serving H / Hkv query heads; q, k
  through RMSNorm0 over the head width, then rotate-half rotary
  (`rope_theta`) over the first `partial_rotary_factor` of it;
  `y = (softmax(q k^T / sqrt(Dh) + causal) v * sigmoid(gate)) W_o`.
  Computed a block of queries at a time: 8192 x 8192 scores of all
  heads in float32 do not fit otherwise. Same arithmetic.
- Gated delta rule: `[q, k, v, z] = h W_qkvz`, `[b, a] = h W_ba`; `q|k|v`
  through a causal depthwise convolution (width 4, no bias), then SiLU;
  `beta = sigmoid(b)`; `g = -exp(A_log) softplus(a + dt_bias)`; q, k
  divided by their L2 norm (eps 1e-6 under the root), q by sqrt(Dk);
  key head j serves value heads j r .. j r + r - 1. Per value head,
  S_0 = 0: `S <- e^{g_t} S; d_t = beta_t (v_t - S^T k_t); S <- S + k_t
  d_t^T; o_t = S^T q_t` -- ONE TOKEN AT A TIME (`lax.scan` over tokens,
  in segments under `jax.checkpoint` so that a gradient does not keep a
  state a token). `y = (RMSNorm(o_t; w) * SiLU(z_t)) W_out`, w plain.
- Expert layer: `p = softmax(h W_r)` over all routed experts; the
  `num_experts_per_tok` largest; weights divided by their sum over all
  of those (`norm_topk_prob`); `y = sum over chosen AND held of w_e
  SwiGLU_e(h) + sigmoid(h . w_sg) SwiGLU_shared(h)`. The experts held
  are `num_experts` from `first_expert`: what the others would add is
  left out, as on the chip that holds this share. Every held expert is
  applied to every token and masked (dense: no sort, no grouping).
- Loss: mean next-token cross-entropy over the vocabulary held. No
  z-loss and no balance loss (the program adds neither).

Departures from the public implementation, all shared with the program:
W_qkvz's columns are q | k | v | z (the checkpoint interleaves them by
key-head group: a column permutation); the multi-token-prediction module
is absent; weights are random.

It reads the program's parameter tree (`Qwen3Next.init`'s layout) and
the configuration's keys, and nothing else of the program. Use under
`jax.default_matmul_precision("highest")` (`loss_and_gradient` sets it).

`correct` (`check`) holds the program to two numbers at the timed sizes,
on the first batch and the initial parameters: the first step's loss,
and the gradient of every parameter leaf, |g - g_ref|_2 / |g_ref|_2,
worst leaf (two small ones apart: UNSTEADY). The loss at initialisation is ln V plus little and hardly
sees the mixers; the gradients go through every one of them.
`operand_mantissa` is the control: the same equations with the operands
of every product rounded to 3 mantissa bits (float8 e4m3's, the nearest
format below the configuration's bf16 operands, which have 7; with
bfloat16's exponent, so that nothing needs a scale), everything else
float32 as before. `check` must refuse it (`benchmark/tools/
lm_control.py`; `PERF.md` has both readings). The reference with every
tensor HELD in bfloat16 is no control: it is the program's own precision
(bf16 activations, products accumulated in float32), and read the same.

Under a gradient nothing of a layer outlives its backward but its input
(`jax.checkpoint` a mixer, an expert layer, a block of queries, a
segment of the recurrence, an expert's part), and the device holds the
parameters and their gradient a layer an array (`unstack`): 8192 tokens
in float32 then take 8.1 GiB by the chip's compiler, less than the train
step they check.
"""
from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

#: |program loss - float32 reference loss| allowed on the first batch, at
#: the timed sizes. The loss is ln(18 992) = 9.85 at initialisation and
#: the mean of 8191 tokens' errors, so bf16 operands with float32
#: accumulation move it by 1e-4 to 1e-3 (`PERF.md`, PR 32: the readings
#: over seeds); this is `benchmark/reference.py`'s limit for the other
#: training cells. It holds the head, the loss and the finiteness of the
#: forward, and sees no precision and hardly a mixer: that is
#: GRADIENT_TOLERANCE's.
LOSS_TOLERANCE = 0.02
#: |g - g_ref|_2 / |g_ref|_2 allowed in the worst parameter leaf (a leaf
#: is one entry of the program's parameter tree, all its layers
#: together), first batch, initial parameters, timed sizes. Between two
#: readings on the chip (`PERF.md`, PR 32: fifteen seeds, and two): the
#: program's largest, 0.193 (the router; 0.15-0.19 in the three leaves a
#: discrete choice gathers, where bf16 activations also move a token's
#: tenth-best expert across the cut; 0.08-0.13 in the others), and the
#: control's, 0.69-0.71 in its worst leaf and over 0.5 in fifteen more
#: (`operand_mantissa=3`). Rounding reads that high here because it
#: grows with the width: 0.012, 0.027 and 0.10 at hidden 32, 256 and
#: 2048, at any length; the reference with its own operands rounded to
#: bf16's 7 bits reads 0.08-0.11 and 0.14 on the chip where the program
#: reads 0.10-0.13 and 0.18.
GRADIENT_TOLERANCE = 0.3
#: Leaves the limit leaves out: 96 numbers each (a value head a layer),
#: each the sum over 8192 tokens of terms of both signs that a few heads
#: of slow decay carry. The program reads 0.02-0.33 there by seed and the
#: control 0.19-0.43, so no limit separates them; the path they sit on
#: (g = -exp(A_log) softplus(a + dt_bias)) is held through
#: `in_proj_ba`, which makes a. Their gaps are printed with the others.
UNSTEADY = ("['gdn']['A_log']", "['gdn']['dt_bias']")
#: Tokens of the recurrence between two kept states under a gradient.
SEGMENT = 64
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


def _rmsnorm0(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _swiglu(h, w_in, w_out, ein):
    gate_up = ein("sd,dgf->sgf", h, w_in)
    return ein("sf,fd->sd", _silu(gate_up[:, 0]) * gate_up[:, 1], w_out)


def _rotary(x, rot, theta):
    """x [S, H, D]: rotate-half over the first `rot` of D."""
    half = rot // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2 / rot)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    xr = x[..., :rot]
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rotated * sin, x[..., rot:]], -1)


def gated_attention(h, w, cfg, ein=jnp.einsum):
    """h [S, D] of one sequence -> [S, D]."""
    s = h.shape[0]
    eps = cfg["rms_norm_eps"]
    n_h, n_kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    qg = ein("sd,dhtk->shtk", h, w["wq"])
    kv = ein("sd,dthk->sthk", h, w["wkv"])
    rot = int(dh * cfg["partial_rotary_factor"])
    q = _rotary(_rmsnorm0(qg[:, :, 0], w["q_norm"], eps), rot,
                cfg["rope_theta"])
    k = _rotary(_rmsnorm0(kv[:, 0], w["k_norm"], eps), rot, cfg["rope_theta"])
    v = kv[:, 1]
    # query head i reads key/value head i // (H / Hkv)
    k = jnp.repeat(k, n_h // n_kv, axis=1)
    v = jnp.repeat(v, n_h // n_kv, axis=1)
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = ein("qhk,shk->hqs", qb, k) / np.sqrt(dh)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return ein("hqs,shk->qhk", jax.nn.softmax(scores, -1), v)

    o = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, n_h, dh)
    o = o * jax.nn.sigmoid(qg[:, :, 1])
    return ein("shk,hkd->sd", o, w["wo"])


def delta_rule(q, k, v, g, beta, ein=jnp.einsum):
    """The recurrence, token by token: q, k [S, Hv, Dk], v [S, Hv, Dv],
    g, beta [S, Hv] -> o [S, Hv, Dv]."""
    s = q.shape[0]
    pad = -s % SEGMENT

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, None, None] * state
        d_t = b_t[:, None] * (v_t - ein("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d_t[:, None, :]
        return state, ein("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (s + pad) // SEGMENT, SEGMENT, *x.shape[1:])
        for x in (q, k, v, g, beta))   # padded tokens come after every real one
    state0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    _, o = jax.lax.scan(segment, state0, xs)
    return o.reshape(s + pad, *o.shape[2:])[:s]


def gated_delta(h, w, cfg, ein=jnp.einsum):
    """h [S, D] of one sequence -> [S, D]."""
    s = h.shape[0]
    n_k, n_v = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, vd = n_k * dk, n_v * dv
    qkvz = ein("sd,dc->sc", h, w["in_proj_qkvz"])
    ba = ein("sd,dc->sc", h, w["in_proj_ba"])
    mixed, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
    width = w["conv"].shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, mixed.shape[1]), jnp.float32), mixed], 0)
    conv = sum(padded[j:j + s] * w["conv"][j] for j in range(width))
    mixed = _silu(conv)
    q = mixed[:, :kd].reshape(s, n_k, dk)
    k = mixed[:, kd:2 * kd].reshape(s, n_k, dk)
    v = mixed[:, 2 * kd:].reshape(s, n_v, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(x, n_v // n_k, axis=1) for x in (q, k))
    beta = jax.nn.sigmoid(ba[:, :n_v])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, n_v:] + w["dt_bias"])
    o = delta_rule(q, k, v, g, beta, ein)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + cfg["rms_norm_eps"]) * w["norm"]
    o = o * _silu(z.reshape(s, n_v, dv))
    return ein("sc,cd->sd", o.reshape(s, vd), w["out_proj"])


def expert_layer(h, w, cfg, ein=jnp.einsum):
    """h [S, D] -> [S, D]: the held experts' part plus the shared expert."""
    top_k = cfg["num_experts_per_tok"]
    first = cfg.get("first_expert", 0)
    p = jax.nn.softmax(ein("sd,de->se", h, w["router"]), -1)
    top_p, top_e = jax.lax.top_k(p, top_k)
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)

    @jax.checkpoint   # (of no carry: a gradient keeps nothing an expert)
    def part(e, w_in, w_out):
        weight = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), -1)
        return weight[:, None] * _swiglu(h, w_in, w_out, ein)

    def one(y, expert):
        return y + part(*expert), None

    n_held = w["w_in"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(n_held), w["w_in"], w["w_out"]))
    gate = jax.nn.sigmoid(ein("sd,d->s", h, w["shared_gate"]))[:, None]
    return y + gate * _swiglu(h, w["shared_in"], w["shared_out"], ein)


def unstack(params: Dict[str, Any]) -> Dict[str, Any]:
    """`Qwen3Next.init`'s tree with a layer an entry of `layers` (there a
    kind's layers are one leaf, stacked [P, I, ...]). Views, if numpy's."""
    interval = params["norm1"].shape[1]
    layers = []
    for p in range(params["norm1"].shape[0]):
        for i in range(interval):
            kind, at = ("gdn", (p, i)) if i < interval - 1 else ("attn", (p,))
            layers.append({
                "norm1": params["norm1"][p, i],
                kind: jax.tree.map(lambda a: a[at], params[kind]),
                "norm2": params["norm2"][p, i],
                "moe": jax.tree.map(lambda a: a[p, i], params["moe"])})
    return {"layers": layers,
            **{k: params[k] for k in ("tok_embed", "head", "norm_f")}}


def forward(params: Dict[str, Any], tokens: jax.Array,
            cfg: Mapping[str, Any], ein=jnp.einsum) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V], float32 throughout; `params`
    the program's tree or `unstack` of it."""
    if "layers" not in params:
        params = unstack(params)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps = cfg["rms_norm_eps"]
    mixers = {kind: jax.checkpoint(lambda h, w, f=f: f(h, w, cfg, ein))
              for kind, f in (("gdn", gated_delta), ("attn", gated_attention),
                              ("moe", expert_layer))}

    @jax.checkpoint
    def sequence(row):
        x = params["tok_embed"][row]
        for layer in params["layers"]:
            kind = "gdn" if "gdn" in layer else "attn"
            x = x + mixers[kind](_rmsnorm0(x, layer["norm1"], eps), layer[kind])
            x = x + mixers["moe"](_rmsnorm0(x, layer["norm2"], eps),
                                  layer["moe"])
        return ein("sd,dv->sv", _rmsnorm0(x, params["norm_f"], eps),
                    params["head"])

    return jax.lax.map(sequence, tokens)


def loss(params: Dict[str, Any], tokens: jax.Array,
         cfg: Mapping[str, Any], ein=jnp.einsum) -> jax.Array:
    """Mean next-token cross-entropy over [B, S] tokens."""
    logits = forward(params, tokens, cfg, ein)[:, :-1]
    lse = jax.nn.logsumexp(logits, axis=-1)
    target = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - target)


def loss_and_gradient(params: Dict[str, Any], tokens: np.ndarray,
                      cfg: Mapping[str, Any], operand_mantissa=None):
    """(loss, its gradient `unstack`ed, numpy) of a whole batch, one row
    at a time inside. `params`: the program's tree on the HOST (numpy):
    the device gets it a layer an array and returns the gradient so,
    which spares it a copy of every layer sliced from its stack and one
    of every layer's gradient on its way into the stack (3 GiB at the
    timed sizes). `operand_mantissa`: the control."""
    ein = (jnp.einsum if operand_mantissa is None
           else rounded_operands(operand_mantissa))
    fn = jax.jit(jax.value_and_grad(lambda p, t: loss(p, t, cfg, ein)))
    with jax.default_matmul_precision("highest"):
        value, grads = fn(unstack(params), jnp.asarray(tokens))
    return float(value), jax.device_get(grads)


def gradient_gaps(got: Dict[str, Any], want: Dict[str, Any]
                  ) -> Dict[str, float]:
    """{leaf of the program's tree: |g - g_ref|_2 / |g_ref|_2 over all
    its layers}; `got` that tree or `unstack` of it, `want` `unstack`ed,
    both numpy."""
    squares: Dict[str, np.ndarray] = {}
    if "layers" not in got:
        got = unstack(got)
    pairs = zip(jax.tree_util.tree_leaves_with_path(got),
                jax.tree.leaves(want), strict=True)
    for (path, g), w in pairs:
        if path[0].key == "layers":
            path = path[2:]              # ("layers", index, *the leaf's own)
        leaf = jax.tree_util.keystr(path)
        g, w = (np.asarray(x, np.float32).ravel() for x in (g, w))
        squares[leaf] = squares.get(leaf, 0.0) + np.array(
            [np.dot(g - w, g - w), np.dot(w, w)], np.float64)
    return {leaf: float(np.sqrt(num / den))
            for leaf, (num, den) in sorted(squares.items())}


def check(program_loss: float, reference_loss: float,
          gaps: Mapping[str, float]) -> Dict[str, Any]:
    """`correct`: the loss within LOSS_TOLERANCE and the worst leaf's
    gradient (UNSTEADY ones apart) within GRADIENT_TOLERANCE; a gap that
    is not finite fails, in any leaf."""
    gap = abs(program_loss - reference_loss)
    held = {k: v for k, v in gaps.items() if k not in UNSTEADY}
    worst = max(held, key=held.get)
    finite = all(np.isfinite(v) for v in gaps.values())
    return {
        "ok": bool(gap <= LOSS_TOLERANCE and finite
                   and held[worst] <= GRADIENT_TOLERANCE),
        "program": program_loss, "reference": reference_loss, "gap": gap,
        "tolerance": LOSS_TOLERANCE,
        "gradient_gap": held[worst], "gradient_gap_of": worst,
        "gradient_tolerance": GRADIENT_TOLERANCE,
        "gradient_gaps": {k: round(v, 6) for k, v in gaps.items()}}
