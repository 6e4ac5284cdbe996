"""Qwen3-Next: periods of gated-delta-rule layers closed by one gated
softmax-attention layer, every layer followed by a top-k expert layer.

Built from the public `config.json`'s keys (`Qwen3NextConfig.from_keys`;
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), and trained by
`Trainer.fit` as any registry model. With h the block's input after its
norm (all norms in float32):

- `RMSNorm0(x; w) = x rsqrt(mean(x^2) + eps) (1 + w)`, w initialised 0.
  Block: `x += Mixer_l(RMSNorm0(x))`, then `x += MoE(RMSNorm0(x))`; a
  final RMSNorm0, an untied head, no position embedding. Of every
  `full_attention_interval` layers the last is gated attention, the
  others gated delta: the layer loop walks PERIODS, and the parameter
  tree is {"gdn": [P, I-1, ...], "attn": [P, ...], "moe": [P, I, ...]}.
- Gated attention: `[q | gate] = h W_q` a head, `k, v` on fewer heads
  (each serves H / Hkv query heads); q and k pass RMSNorm0 over the head
  width, then rotary (rotate-half) over its first
  `partial_rotary_factor`; `y = (softmax(q k^T / sqrt(Dh) + causal) v
  * sigmoid(gate)) W_o`. The attention itself is `models/attention.py`'s
  dispatcher (the flash kernels on the chip), K/V repeated to H heads.
- Gated delta rule: `[q, k, v, z] = h W_qkvz`, `[b, a] = h W_ba`;
  `q | k | v` pass a causal depthwise convolution (no bias) and SiLU;
  `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`; q, k
  L2-normalised, q scaled by 1/sqrt(Dk); the rule of
  `ops/gated_delta.py`; `y = (RMSNorm(o; w) * SiLU(z)) W_out`, that norm
  over a head's width with a plain weight initialised 1. (The public
  checkpoint interleaves W_qkvz's columns by key-head group; here they
  are q | k | v | z, a column permutation of the same matrix.)
- Expert layer: `models/moe.py`, told the experts held
  (`num_experts` from `first_expert`, of `num_experts_routed`), plus one
  shared expert behind a sigmoid gate.
- Loss: mean next-token cross-entropy over the vocabulary held
  (`models/gpt.py`'s sums and head; no z-loss, no balance loss: the
  public config gives no coefficient).

What sits between a block's norm and its residual is a mixer, of either
kind; the delta mixer carries a recurrent state along the sequence
(serving it, with that state beside the KV pages, is not written yet).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from determined_tpu.models import moe
from determined_tpu.models.base import Metrics, Model
from determined_tpu.models.gpt import (
    _remat_policy,
    head_logits,
    next_token_sums,
)
from determined_tpu.ops.gated_delta import gated_delta_chunked

# The module: the package re-exports the function `attention` under its name.
attn_mod = importlib.import_module("determined_tpu.models.attention")
#: Tile of the blocked flash kernels (fitted down to shorter sequences).
_FLASH_BLOCK = 512
_ACT = P(attn_mod.BATCH_AXES, "context", None)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The public config.json's keys, under their names. `num_experts` is
    the number of experts HELD here, `first_expert` the first of them, and
    `num_experts_routed` the router's width (0: all are held)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rms_norm_eps: float = 1e-6
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    num_experts: int = 512
    num_experts_routed: int = 0
    first_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16          # compute; masters are float32

    #: Public keys whose only supported value is the published one: a
    #: configuration that differs is another architecture.
    _FIXED = {
        "model_type": "qwen3_next", "hidden_act": "silu",
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "tie_word_embeddings": False, "use_sliding_window": False,
        "rope_scaling": None,
    }

    @classmethod
    def from_keys(cls, keys: Mapping[str, Any]) -> "Qwen3NextConfig":
        """From a config.json's keys: those that are fields are taken,
        those in `_FIXED` are checked, the rest (documentation, sizes no
        layer of this model uses) are passed over."""
        for key, want in cls._FIXED.items():
            if key in keys and keys[key] != want:
                raise ValueError(
                    f"qwen3-next: {key}={keys[key]!r} is not supported "
                    f"(only {want!r})")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in keys.items() if k in names})

    @property
    def n_routed(self) -> int:
        return self.num_experts_routed or self.num_experts

    @property
    def n_periods(self) -> int:
        return self.num_hidden_layers // self.full_attention_interval

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def gdn_key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def gdn_value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim


def _rmsnorm0(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rotary(x: jax.Array, rot: int, theta: float) -> jax.Array:
    """Rotate-half rotary over the first `rot` of x [B, S, H, D]'s width,
    positions 0..S-1; the rest untouched."""
    s, half = x.shape[1], rot // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x32[..., rot:]],
        axis=-1).astype(x.dtype)


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution along S: y_t = sum_j w[j] x_{t-K+1+j},
    x [B, S, C], w [K, C], zeros before the sequence."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + s] * w[j] for j in range(k))


def _output_gate(o: jax.Array, gate: jax.Array) -> jax.Array:
    """o * sigmoid(gate): the attention layer's gate on its output, a head
    and channel at a time."""
    return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


def _write_strength(b: jax.Array) -> jax.Array:
    """beta = sigmoid(b): how much of the delta a token writes."""
    return jax.nn.sigmoid(b)


class Qwen3Next(Model):
    """batch = {"tokens": int32 [B, S]} (next-token loss), optional
    "loss_mask" [B, S]."""

    input_contract = "tokens"

    def __init__(self, config: Qwen3NextConfig,
                 mesh: Optional[Mesh] = None) -> None:
        c = config
        if c.num_hidden_layers % c.full_attention_interval:
            raise ValueError(
                f"{c.num_hidden_layers} layers are not whole periods of "
                f"{c.full_attention_interval}")
        if not 0 <= c.first_expert <= c.n_routed - c.num_experts:
            raise ValueError(
                f"experts {c.first_expert}..+{c.num_experts} are not "
                f"among the {c.n_routed} routed over")
        self.config = config
        self.mesh = mesh

    # -- params ------------------------------------------------------------
    def init(self, rng: jax.Array) -> Dict[str, Any]:
        c = self.config
        d, pd = c.hidden_size, jnp.float32
        p, i = c.n_periods, c.full_attention_interval
        h, hkv, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        hv, kd, vd = c.linear_num_value_heads, c.gdn_key_dim, c.gdn_value_dim
        e, f, fs = (c.num_experts, c.moe_intermediate_size,
                    c.shared_expert_intermediate_size)
        keys = iter(jax.random.split(rng, 16))
        normal = jax.nn.initializers.normal(0.02)

        def w(*shape):
            return normal(next(keys), shape, pd)

        return {
            "tok_embed": w(c.vocab_size, d),
            "head": w(d, c.vocab_size),
            "norm_f": jnp.zeros((d,), pd),
            "norm1": jnp.zeros((p, i, d), pd),
            "norm2": jnp.zeros((p, i, d), pd),
            "gdn": {
                "in_proj_qkvz": w(p, i - 1, d, 2 * kd + 2 * vd),
                "in_proj_ba": w(p, i - 1, d, 2 * hv),
                "conv": w(p, i - 1, c.linear_conv_kernel_dim, 2 * kd + vd),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (p, i - 1, hv), pd, 1e-3, 16.0)),
                "dt_bias": jnp.ones((p, i - 1, hv), pd),
                "norm": jnp.ones((p, i - 1, c.linear_value_head_dim), pd),
                "out_proj": w(p, i - 1, vd, d),
            },
            "attn": {
                "wq": w(p, d, h, 2, dh),
                "wkv": w(p, d, 2, hkv, dh),
                "q_norm": jnp.zeros((p, dh), pd),
                "k_norm": jnp.zeros((p, dh), pd),
                "wo": w(p, h, dh, d),
            },
            "moe": {
                "router": w(p, i, d, c.n_routed),
                "w_in": w(p, i, e, d, 2, f),
                "w_out": w(p, i, e, f, d),
                "shared_gate": w(p, i, d),
                "shared_in": w(p, i, d, 2, fs),
                "shared_out": w(p, i, fs, d),
            },
        }

    def logical_axes(self) -> Dict[str, Any]:
        L = "layers"
        return {
            "tok_embed": ("vocab", "embed"),
            "head": ("embed", "vocab"),
            "norm_f": ("norm",),
            "norm1": (L, L, "norm"),
            "norm2": (L, L, "norm"),
            "gdn": {
                "in_proj_qkvz": (L, L, "embed", "heads"),
                "in_proj_ba": (L, L, "embed", None),
                "conv": (L, L, None, "heads"),
                "A_log": (L, L, None),
                "dt_bias": (L, L, None),
                "norm": (L, L, "norm"),
                "out_proj": (L, L, "heads", "embed"),
            },
            "attn": {
                "wq": (L, "embed", "heads", None, "head_dim"),
                "wkv": (L, "embed", None, "kv", "head_dim"),
                "q_norm": (L, "norm"),
                "k_norm": (L, "norm"),
                "wo": (L, "heads", "head_dim", "embed"),
            },
            "moe": {
                "router": (L, L, "embed", None),
                "w_in": (L, L, "expert", "embed", None, "mlp"),
                "w_out": (L, L, "expert", "mlp", "embed"),
                "shared_gate": (L, L, "embed"),
                "shared_in": (L, L, "embed", None, "mlp"),
                "shared_out": (L, L, "mlp", "embed"),
            },
        }

    # -- forward -----------------------------------------------------------
    def _constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def _gdn_half(self, x: jax.Array, norm: jax.Array,
                  w: Dict[str, jax.Array]) -> jax.Array:
        """x + GatedDelta(RMSNorm0(x))."""
        c = self.config
        b, s, _ = x.shape
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv, kd = c.linear_key_head_dim, c.linear_value_head_dim, c.gdn_key_dim
        with jax.named_scope("attn"), jax.named_scope("gdn"):
            h = _rmsnorm0(x, norm, c.rms_norm_eps)
            qkvz = jnp.dot(h, w["in_proj_qkvz"].astype(c.dtype))
            ba = jnp.dot(h, w["in_proj_ba"].astype(c.dtype),
                         preferred_element_type=jnp.float32)
            qkv = jax.nn.silu(_causal_conv(
                qkvz[..., :2 * kd + c.gdn_value_dim],
                w["conv"].astype(c.dtype)))
            z = qkvz[..., 2 * kd + c.gdn_value_dim:].reshape(b, s, hv, dv)
            q, k = (qkv[..., j * kd:(j + 1) * kd].reshape(b, s, hk, dk)
                    for j in (0, 1))
            v = qkv[..., 2 * kd:].reshape(b, s, hv, dv)

            def l2(x):
                x32 = x.astype(jnp.float32)
                return x32 * lax.rsqrt(
                    jnp.sum(jnp.square(x32), -1, keepdims=True) + 1e-6)

            q = (l2(q) * dk ** -0.5).astype(c.dtype)
            k = l2(k).astype(c.dtype)
            beta = _write_strength(ba[..., :hv])
            g = -jnp.exp(w["A_log"].astype(jnp.float32)) * jax.nn.softplus(
                ba[..., hv:] + w["dt_bias"].astype(jnp.float32))
            o = gated_delta_chunked(q, k, v, g, beta)
            o32 = o.astype(jnp.float32)
            o32 = o32 * lax.rsqrt(
                jnp.mean(jnp.square(o32), -1, keepdims=True) + c.rms_norm_eps)
            o = (o32 * w["norm"].astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32))).astype(c.dtype)
            y = jnp.dot(o.reshape(b, s, hv * dv),
                        w["out_proj"].astype(c.dtype))
            return self._constrain(x + y, _ACT)

    def _attn_half(self, x: jax.Array, norm: jax.Array,
                   w: Dict[str, jax.Array]) -> jax.Array:
        """x + GatedAttention(RMSNorm0(x)). The `attn` and `gated_attn`
        scopes close around the attention call: the flash kernels keep
        the names they have under no scope (`ops/flash_attention.py`),
        as in `GPT._attn_half`."""
        c = self.config
        rep = c.num_attention_heads // c.num_key_value_heads
        with jax.named_scope("attn"), jax.named_scope("gated_attn"):
            h = _rmsnorm0(x, norm, c.rms_norm_eps)
            qg = jnp.einsum("bsd,dhtk->bshtk", h, w["wq"].astype(c.dtype))
            kv = jnp.einsum("bsd,dthk->bsthk", h, w["wkv"].astype(c.dtype))
            q = _rmsnorm0(qg[:, :, :, 0], w["q_norm"], c.rms_norm_eps)
            k = _rmsnorm0(kv[:, :, 0], w["k_norm"], c.rms_norm_eps)
            q = _rotary(q, c.rotary_dim, c.rope_theta)
            k = _rotary(k, c.rotary_dim, c.rope_theta)
            k, v = (jnp.repeat(x, rep, axis=2) for x in (k, kv[:, :, 1]))
        o = attn_mod.attention(
            q, k, v, mesh=self.mesh, causal=True,
            block_q=_FLASH_BLOCK, block_k=_FLASH_BLOCK)
        with jax.named_scope("attn"), jax.named_scope("gated_attn"):
            o = _output_gate(o, qg[:, :, :, 1])
            y = jnp.einsum("bshk,hkd->bsd", o, w["wo"].astype(c.dtype))
            return self._constrain(x + y, _ACT)

    def _moe_local(self, h: jax.Array, w: Dict[str, jax.Array]):
        """[b, S, D] of one batch shard -> (y, counters [1, 2])."""
        c = self.config
        cast = lambda name: w[name].astype(c.dtype)  # noqa: E731
        flat = h.reshape(-1, h.shape[-1])
        y, counters = moe.expert_layer(
            flat, w["router"], cast("w_in"), cast("w_out"),
            top_k=c.num_experts_per_tok, first_expert=c.first_expert,
            normalize=c.norm_topk_prob)
        y = y + moe.shared_expert(
            flat, w["shared_gate"], cast("shared_in"), cast("shared_out"))
        return y.reshape(h.shape), jnp.stack(
            [counters["held_rows"] / flat.shape[0],
             counters["load_max_over_mean"]])[None]

    @jax.named_scope("mlp")
    def _moe_half(self, x: jax.Array, norm: jax.Array,
                  w: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
        """x + MoE(RMSNorm0(x)) -> (x, [held rows a token, load max over
        mean]). On a mesh each batch shard routes its own tokens through
        its copy of the experts held (no exchange), and the counters are
        the shards' means."""
        h = _rmsnorm0(x, norm, self.config.rms_norm_eps)
        y, counters = moe.on_batch_shards(
            self._moe_local, h, w, self.mesh, attn_mod.BATCH_AXES)
        return self._constrain(x + y, _ACT), counters

    def _forward_trunk(self, params: Dict[str, Any], tokens: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
        """-> (hidden [B, S, D] before the final norm, the expert layers'
        counters [2], mean over layers)."""
        c = self.config
        with jax.named_scope("embed"):
            tokens = self._constrain(tokens, P(attn_mod.BATCH_AXES, "context"))
            table = self._constrain(
                params["tok_embed"].astype(c.dtype), P(None, None))
            x = self._constrain(table[tokens], _ACT)

        # The delta mixer keeps its projections (the dots policy) and
        # repeats the chunked rule in the backward; the expert layer
        # repeats its routing and grouped matmuls. The attention layer
        # stays outside, as in GPT: its flash residuals are cheaper to
        # keep than to rebuild.
        gdn_half = jax.checkpoint(self._gdn_half, policy=_remat_policy())
        moe_half = jax.checkpoint(self._moe_half, policy=_remat_policy())

        def layer(tree, *index):
            return jax.tree.map(lambda a: a[index], tree)

        counters = jnp.zeros((2,), jnp.float32)
        last = c.full_attention_interval - 1
        for p in range(c.n_periods):
            for i in range(c.full_attention_interval):
                norm1 = params["norm1"][p, i]
                if i < last:
                    x = gdn_half(x, norm1, layer(params["gdn"], p, i))
                else:
                    x = self._attn_half(x, norm1, layer(params["attn"], p))
                x, layer_counters = moe_half(
                    x, params["norm2"][p, i], layer(params["moe"], p, i))
                counters = counters + layer_counters
        return x, counters / c.num_hidden_layers

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, V] (compute dtype)."""
        return self._logits(params, self._forward_trunk(params, tokens)[0])

    def _logits(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        c = self.config
        with jax.named_scope("head_loss"):
            h = _rmsnorm0(x, params["norm_f"], c.rms_norm_eps)
            logits = head_logits(h, params["head"].astype(c.dtype))
            return self._constrain(
                logits, P(attn_mod.BATCH_AXES, "context", "tensor"))

    # -- loss --------------------------------------------------------------
    def loss(self, params: Dict[str, Any], batch: Dict[str, jax.Array],
             rng: jax.Array) -> Tuple[jax.Array, Metrics]:
        del rng
        tokens = batch["tokens"]
        mask = batch.get("loss_mask")
        mask = (jnp.ones(tokens.shape, jnp.float32) if mask is None
                else mask.astype(jnp.float32))
        x, counters = self._forward_trunk(params, tokens)
        logits = self._logits(params, x)
        with jax.named_scope("head_loss"):
            nll_sum, _z, acc_sum, n_tok = next_token_sums(
                logits.astype(jnp.float32), tokens, mask)
            n = jnp.maximum(n_tok, 1.0)
            loss = nll_sum / n
        return loss, {
            "loss": loss, "accuracy": acc_sum / n, "tokens": n_tok,
            "moe_held_rows_per_token": counters[0],
            "moe_load_max_over_mean": counters[1],
        }
