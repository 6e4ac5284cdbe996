"""GLM-4.7-Flash (`model_type` `glm4_moe_lite`): latent attention (MLA)
in every layer, leading dense layers and then expert layers whose router
scores by a sigmoid and chooses with a selection bias, and one
multi-token-prediction (MTP) module. The DeepSeek-V3 block at other
numbers.

Built from the public `config.json`'s keys (`Glm4MoeLiteConfig.from_keys`;
https://huggingface.co/zai-org/GLM-4.7-Flash), and trained by
`Trainer.fit` as any registry model. With h the block's input after its
norm (all norms in float32):

- `RMSNorm(x; w) = x rsqrt(mean(x^2) + eps) w`, w initialised 1. Block:
  `x += MLA(RMSNorm(x))`, then `x += FFN_l(RMSNorm(x))`; a final
  RMSNorm, an untied head, no biases, no position embedding.
- MLA: `c_q = RMSNorm(h W_qa)`, `[q_nope | q_r] = c_q W_qb` a head;
  `[c_kv | k_r] = h W_kva`, `c_kv = RMSNorm(c_kv)`,
  `[k_nope | v] = c_kv W_kvb` a head. Rotary (rotate-half, this repo's
  layout: the public checkpoint interleaves the pairs, a column
  permutation of W_qb and W_kva) on every head's `q_r` and on the ONE
  `k_r`, which all heads share: `q = [q_nope | q_r]`,
  `k = [k_nope | k_r]`. `y = softmax(q k^T / sqrt(Dqk) + causal) v W_o`.
  Keys and values are both `v_head_dim` wide here, so the attention
  itself is `models/attention.py`'s dispatcher (the flash kernels on the
  chip), all heads whole.
- The first `first_k_dense_replace` layers' FFN is dense SwiGLU; the
  others are `models/moe.py`'s expert layer, told the experts held
  (`n_routed_experts` from `first_expert`, of `num_experts_routed`):
  `s = sigmoid(h W_r)`, the k largest `s + b` chosen (b the selection
  bias: a parameter no gradient reaches, initialised 0; the rule that
  moves it in the published run is in no public key and is not
  invented), weights `routed_scaling_factor s_e / (sum of the k + 1e-20)`,
  plus `n_shared_experts` ungated shared experts as one SwiGLU.
- MTP module (`num_nextn_predict_layers` 1; 0: absent, and its loss
  term with it): `h'_i = W_eh [RMSNorm(Emb(t_{i+1})) | RMSNorm(x_i)]`,
  x the trunk's last hidden state before the final norm; one more block
  (MLA + expert layer); its own final norm; the model's own embedding
  and head: the logits for `t_{i+2}`. It keeps the trunk's length: the
  last position is fed a token that is not there and predicts nothing
  (causal: no other position sees it).
- Loss: `CE(t_{i+1}) + mtp_loss_weight CE_mtp(t_{i+2})`, each the mean
  over its own targets of the vocabulary held (no z-loss, no balance
  loss: the public config gives no coefficient).

The stack is not periodic, so the parameter tree is a stack a KIND of
half-block: `attn` [L, ...], `dense` [K, ...], `moe` [L - K, ...], and
the MTP module's own block under `mtp`.

Scopes (`models/base.py`): both halves open `attn` / `mlp`; inside,
`mla` is the whole mixer but the flash kernels (which carry no scope:
`ops/flash_attention.py`), and `mtp` everything of the module, its
head pass and loss term too.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
from typing import Any, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from determined_tpu.models import moe
from determined_tpu.models.base import Metrics, Model
from determined_tpu.models.gpt import _remat_policy, head_logits
from determined_tpu.models.qwen3_next import _FLASH_BLOCK, _rotary
from determined_tpu.ops.fused_cross_entropy import fused_next_token_sums

# The module: the package re-exports the function `attention` under its name.
attn_mod = importlib.import_module("determined_tpu.models.attention")
_ACT = P(attn_mod.BATCH_AXES, "context", None)
#: Vocabulary columns a chunk of the fused head-and-loss (the held
#: 19 360 rows are 4 chunks of 4 840).
_VOCAB_CHUNK = 4840


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """The public config.json's keys, under their names. `n_routed_experts`
    is the number of experts HELD here, `first_expert` the first of them,
    and `num_experts_routed` the router's width (0: all are held)."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    num_key_value_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 10240
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    num_experts_routed: int = 0
    first_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    num_nextn_predict_layers: int = 1
    #: weight of the MTP term in the loss (no public key: the family's
    #: reports give 0.3, later 0.1)
    mtp_loss_weight: float = 0.3
    dtype: Any = jnp.bfloat16          # compute; masters are float32

    #: Public keys whose only supported value is the published one: a
    #: configuration that differs is another architecture.
    _FIXED = {
        "model_type": "glm4_moe_lite", "hidden_act": "silu",
        "attention_bias": False, "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "tie_word_embeddings": False, "rope_scaling": None,
        "partial_rotary_factor": 1,
    }

    @classmethod
    def from_keys(cls, keys: Mapping[str, Any]) -> "Glm4MoeLiteConfig":
        """From a config.json's keys: those that are fields are taken,
        those in `_FIXED` are checked, the rest (documentation, sizes no
        layer of this model uses) are passed over."""
        for key, want in cls._FIXED.items():
            if key in keys and keys[key] != want:
                raise ValueError(
                    f"glm4-moe-lite: {key}={keys[key]!r} is not supported "
                    f"(only {want!r})")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in keys.items() if k in names})

    @property
    def n_routed(self) -> int:
        return self.num_experts_routed or self.n_routed_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


def _rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _q_latent_norm(c_q: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """The norm between the two query projections."""
    return _rmsnorm(c_q, w, eps)


def _shared_rotary_key(k_r: jax.Array, heads: int, theta: float) -> jax.Array:
    """k_r [B, S, R], the one rotary key a token: rotated, then the same
    for every head -> [B, S, H, R]."""
    k_r = _rotary(k_r[:, :, None, :], k_r.shape[-1], theta)
    return jnp.broadcast_to(k_r, (*k_r.shape[:2], heads, k_r.shape[-1]))


@contextlib.contextmanager
def _scopes(*names: str):
    with contextlib.ExitStack() as stack:
        for name in names:
            stack.enter_context(jax.named_scope(name))
        yield


class _Leaf(NamedTuple):
    """A parameter leaf: its shape, its logical axes, and how it starts
    (`normal`: N(0, 0.02); `ones`; `zeros`)."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    fill: str = "normal"


def _is_leaf(x: Any) -> bool:
    return isinstance(x, _Leaf)


def _layer(tree: Any, index: int) -> Any:
    return jax.tree.map(lambda a: a[index], tree)


class Glm4MoeLite(Model):
    """batch = {"tokens": int32 [B, S]} (next-token loss, and the MTP
    module's on the token after), optional "loss_mask" [B, S]."""

    input_contract = "tokens"

    def __init__(self, config: Glm4MoeLiteConfig,
                 mesh: Optional[Mesh] = None) -> None:
        c = config
        if c.num_key_value_heads != c.num_attention_heads:
            raise ValueError(
                f"latent attention has no grouped heads: "
                f"num_key_value_heads={c.num_key_value_heads} is not "
                f"num_attention_heads={c.num_attention_heads}")
        if c.v_head_dim != c.qk_head_dim:
            raise ValueError(
                f"v_head_dim={c.v_head_dim} is not qk_nope_head_dim + "
                f"qk_rope_head_dim={c.qk_head_dim}: no kernel here takes "
                "keys and values of unequal width")
        if not 0 <= c.first_k_dense_replace < c.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace={c.first_k_dense_replace} leaves no "
                f"expert layer among {c.num_hidden_layers}")
        if not 0 <= c.first_expert <= c.n_routed - c.n_routed_experts:
            raise ValueError(
                f"experts {c.first_expert}..+{c.n_routed_experts} are not "
                f"among the {c.n_routed} routed over")
        if c.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers={c.num_nextn_predict_layers}: "
                "one MTP module or none")
        self.config = config
        self.mesh = mesh

    # -- params ------------------------------------------------------------
    def _block_leaves(self, n_attn: int, n_moe: int) -> Dict[str, Any]:
        """The leaves of `n_attn` MLA halves and `n_moe` expert layers,
        stacked."""
        c = self.config
        d, h, L = c.hidden_size, c.num_attention_heads, "layers"
        e, f = c.n_routed_experts, c.moe_intermediate_size
        fs = c.n_shared_experts * f
        return {
            "norm1": _Leaf((n_attn, d), (L, "norm"), "ones"),
            "norm2": _Leaf((n_attn, d), (L, "norm"), "ones"),
            "attn": {
                "wq_a": _Leaf((n_attn, d, c.q_lora_rank), (L, "embed", None)),
                "q_norm": _Leaf((n_attn, c.q_lora_rank), (L, "norm"), "ones"),
                "wq_b": _Leaf((n_attn, c.q_lora_rank, h, c.qk_head_dim),
                              (L, None, "heads", "head_dim")),
                "wkv_a": _Leaf(
                    (n_attn, d, c.kv_lora_rank + c.qk_rope_head_dim),
                    (L, "embed", None)),
                "kv_norm": _Leaf((n_attn, c.kv_lora_rank), (L, "norm"),
                                 "ones"),
                "wkv_b": _Leaf((n_attn, c.kv_lora_rank, h,
                                c.qk_nope_head_dim + c.v_head_dim),
                               (L, None, "heads", "head_dim")),
                "wo": _Leaf((n_attn, h, c.v_head_dim, d),
                            (L, "heads", "head_dim", "embed")),
            },
            "moe": {
                "router": _Leaf((n_moe, d, c.n_routed), (L, "embed", None)),
                "bias": _Leaf((n_moe, c.n_routed), (L, None), "zeros"),
                "w_in": _Leaf((n_moe, e, d, 2, f),
                              (L, "expert", "embed", None, "mlp")),
                "w_out": _Leaf((n_moe, e, f, d),
                               (L, "expert", "mlp", "embed")),
                "shared_in": _Leaf((n_moe, d, 2, fs),
                                   (L, "embed", None, "mlp")),
                "shared_out": _Leaf((n_moe, fs, d), (L, "mlp", "embed")),
            },
        }

    def _leaves(self) -> Dict[str, Any]:
        c = self.config
        d, k, L = c.hidden_size, c.first_k_dense_replace, "layers"
        tree = {
            "tok_embed": _Leaf((c.vocab_size, d), ("vocab", "embed")),
            "head": _Leaf((d, c.vocab_size), ("embed", "vocab")),
            "norm_f": _Leaf((d,), ("norm",), "ones"),
            **self._block_leaves(c.num_hidden_layers, c.n_expert_layers),
        }
        if k:
            tree["dense"] = {
                "w_in": _Leaf((k, d, 2, c.intermediate_size),
                              (L, "embed", None, "mlp")),
                "w_out": _Leaf((k, c.intermediate_size, d),
                               (L, "mlp", "embed")),
            }
        if c.num_nextn_predict_layers:
            tree["mtp"] = {
                "enorm": _Leaf((d,), ("norm",), "ones"),
                "hnorm": _Leaf((d,), ("norm",), "ones"),
                "eh_proj": _Leaf((2 * d, d), (None, "embed")),
                "norm_f": _Leaf((d,), ("norm",), "ones"),
                **self._block_leaves(1, 1),
            }
        return tree

    @staticmethod
    def _init_tree(leaves: Dict[str, Any], rng: jax.Array):
        flat, treedef = jax.tree.flatten(leaves, is_leaf=_is_leaf)
        normal = jax.nn.initializers.normal(0.02)
        return jax.tree.unflatten(treedef, [
            normal(key, leaf.shape, jnp.float32) if leaf.fill == "normal"
            else getattr(jnp, leaf.fill)(leaf.shape, jnp.float32)
            for key, leaf in zip(jax.random.split(rng, len(flat)), flat)])

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        """(The trunk's weights do not depend on whether the MTP module
        is there.)"""
        leaves = self._leaves()
        trunk_key, mtp_key = jax.random.split(rng)
        mtp = leaves.pop("mtp", None)
        params = self._init_tree(leaves, trunk_key)
        if mtp is not None:
            params["mtp"] = self._init_tree(mtp, mtp_key)
        return params

    def logical_axes(self) -> Dict[str, Any]:
        return jax.tree.map(lambda leaf: leaf.axes, self._leaves(),
                            is_leaf=_is_leaf)

    # -- forward -----------------------------------------------------------
    def _constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def _mla_qkv(self, outer: Tuple[str, ...], x: jax.Array, norm: jax.Array,
                 w: Dict[str, jax.Array]):
        """RMSNorm(x) through the latent projections -> q, k, v, each
        [B, S, H, Dqk]."""
        c = self.config
        nope, heads = c.qk_nope_head_dim, c.num_attention_heads
        cast = lambda name: w[name].astype(c.dtype)  # noqa: E731
        with _scopes(*outer, "attn", "mla"):
            h = _rmsnorm(x, norm, c.rms_norm_eps)
            c_q = _q_latent_norm(
                jnp.dot(h, cast("wq_a")), w["q_norm"], c.rms_norm_eps)
            q = jnp.einsum("bsr,rhk->bshk", c_q, cast("wq_b"))
            kv_a = jnp.dot(h, cast("wkv_a"))
            c_kv = _rmsnorm(
                kv_a[..., :c.kv_lora_rank], w["kv_norm"], c.rms_norm_eps)
            kv = jnp.einsum("bsr,rhk->bshk", c_kv, cast("wkv_b"))
            q = jnp.concatenate(
                [q[..., :nope],
                 _rotary(q[..., nope:], c.qk_rope_head_dim, c.rope_theta)],
                axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope], _shared_rotary_key(
                    kv_a[..., c.kv_lora_rank:], heads, c.rope_theta)],
                axis=-1)
            return q, k, kv[..., nope:]

    def _mla_half(self, x: jax.Array, norm: jax.Array,
                  w: Dict[str, jax.Array], outer: Tuple[str, ...] = ()
                  ) -> jax.Array:
        """x + MLA(RMSNorm(x)). The projections are rebuilt in the
        backward from x (q, k and v stay, as the flash kernels'
        residuals: the dots policy would keep 0.25 GiB a layer more at
        8192 tokens, which the chip's 16 GB do not have beside 11.3 GB
        of state); the scopes close around the attention call, which
        keeps the kernels the names they have under no scope and no
        `checkpoint`, as in `GPT._attn_half`."""
        c = self.config
        q, k, v = jax.checkpoint(self._mla_qkv, static_argnums=(0,))(
            outer, x, norm, w)
        o = attn_mod.attention(
            q, k, v, mesh=self.mesh, causal=True,
            block_q=_FLASH_BLOCK, block_k=_FLASH_BLOCK)
        with _scopes(*outer, "attn", "mla"):
            y = jnp.einsum("bshk,hkd->bsd", o, w["wo"].astype(c.dtype))
            return self._constrain(x + y, _ACT)

    def _dense_half(self, x: jax.Array, norm: jax.Array,
                    w: Dict[str, jax.Array]) -> jax.Array:
        """x + SwiGLU(RMSNorm(x)), a leading layer's FFN."""
        c = self.config
        with jax.named_scope("mlp"):
            h = _rmsnorm(x, norm, c.rms_norm_eps)
            y = moe.swiglu(h.reshape(-1, h.shape[-1]),
                           w["w_in"].astype(c.dtype),
                           w["w_out"].astype(c.dtype))
            return self._constrain(x + y.reshape(x.shape), _ACT)

    def _moe_local(self, h: jax.Array, w: Dict[str, jax.Array]):
        """[b, S, D] of one batch shard -> (y, counters [1, 2])."""
        c = self.config
        cast = lambda name: w[name].astype(c.dtype)  # noqa: E731
        flat = h.reshape(-1, h.shape[-1])
        y, counters = moe.expert_layer(
            flat, w["router"], cast("w_in"), cast("w_out"),
            top_k=c.num_experts_per_tok, first_expert=c.first_expert,
            normalize=c.norm_topk_prob, score=moe.sigmoid_scores,
            bias=w["bias"], scale=c.routed_scaling_factor, norm_eps=1e-20)
        y = y + moe.shared_expert(
            flat, None, cast("shared_in"), cast("shared_out"))
        return y.reshape(h.shape), jnp.stack(
            [counters["held_rows"] / flat.shape[0],
             counters["load_max_over_mean"]])[None]

    def _moe_half(self, outer: Tuple[str, ...], x: jax.Array,
                  norm: jax.Array, w: Dict[str, jax.Array]
                  ) -> Tuple[jax.Array, jax.Array]:
        """x + MoE(RMSNorm(x)) -> (x, [held rows a token, load max over
        mean]); on a mesh a batch shard routes its own tokens."""
        with _scopes(*outer, "mlp"):
            h = _rmsnorm(x, norm, self.config.rms_norm_eps)
            y, counters = moe.on_batch_shards(
                self._moe_local, h, w, self.mesh, attn_mod.BATCH_AXES)
            return self._constrain(x + y, _ACT), counters

    def _embed(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        tokens = self._constrain(tokens, P(attn_mod.BATCH_AXES, "context"))
        table = self._constrain(
            params["tok_embed"].astype(self.config.dtype), P(None, None))
        return self._constrain(table[tokens], _ACT)

    def _forward_trunk(self, params: Dict[str, Any], tokens: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
        """-> (hidden [B, S, D] before the final norm, the expert layers'
        counters [2], summed over layers)."""
        c = self.config
        with jax.named_scope("embed"):
            x = self._embed(params, tokens)
        # Both FFN halves keep their matmuls' outputs (the dots policy, as
        # `models/qwen3_next.py`); the expert layer repeats its routing
        # and grouped matmuls. (The MLA half keeps nothing but what the
        # flash kernels keep: `_mla_half`.)
        dense_half = jax.checkpoint(self._dense_half, policy=_remat_policy())
        moe_half = jax.checkpoint(self._moe_half, static_argnums=(0,),
                                  policy=_remat_policy())
        counters = jnp.zeros((2,), jnp.float32)
        for i in range(c.num_hidden_layers):
            x = self._mla_half(x, params["norm1"][i],
                               _layer(params["attn"], i))
            norm2, e = params["norm2"][i], i - c.first_k_dense_replace
            if e < 0:
                x = dense_half(x, norm2, _layer(params["dense"], i))
            else:
                x, layer_counters = moe_half(
                    (), x, norm2, _layer(params["moe"], e))
                counters = counters + layer_counters
        return x, counters

    def _mtp_trunk(self, params: Dict[str, Any], x: jax.Array,
                   tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """The module's hidden states before its final norm: position i
        holds what predicts token i + 2 (the last position none)."""
        c, w = self.config, params["mtp"]
        with _scopes("embed", "mtp"):
            following = self._embed(params, jnp.roll(tokens, -1, axis=1))
            both = jnp.concatenate(
                [_rmsnorm(following, w["enorm"], c.rms_norm_eps),
                 _rmsnorm(x, w["hnorm"], c.rms_norm_eps)], axis=-1)
            x = self._constrain(
                jnp.dot(both, w["eh_proj"].astype(c.dtype)), _ACT)
        x = self._mla_half(x, w["norm1"][0], _layer(w["attn"], 0), ("mtp",))
        return jax.checkpoint(
            self._moe_half, static_argnums=(0,), policy=_remat_policy())(
                ("mtp",), x, w["norm2"][0], _layer(w["moe"], 0))

    def _final(self, params: Dict[str, Any], x: jax.Array,
               norm: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """-> (the final norm of x, the head in the compute dtype)."""
        c = self.config
        return (_rmsnorm(x, norm, c.rms_norm_eps),
                params["head"].astype(c.dtype))

    def apply(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, V] (compute dtype)."""
        x = self._forward_trunk(params, tokens)[0]
        with jax.named_scope("head_loss"):
            logits = head_logits(*self._final(params, x, params["norm_f"]))
            return self._constrain(
                logits, P(attn_mod.BATCH_AXES, "context", "tensor"))

    # -- loss --------------------------------------------------------------
    def _shifted_loss(self, params, x, norm, tokens, mask, shift: int):
        """Mean cross-entropy of position i's logits against token
        i + shift -> (loss, accuracy, targets counted), by the chunked
        head-and-loss of `ops/fused_cross_entropy.py` (`GPT._loss_fused`'s):
        [S, V] logits in float32 are 0.6 GiB a pass here, and with two
        passes a step they are what the MTP module's block would not fit
        beside."""
        h, w_out = self._final(params, x, norm)
        nll_sum, _nll, _z, acc_sum, n_tok = fused_next_token_sums(
            h[:, :-shift], w_out, tokens[:, shift:], mask[:, shift:],
            target_chunk=_VOCAB_CHUNK)
        n = jnp.maximum(n_tok, 1.0)
        return nll_sum / n, acc_sum / n, n_tok

    def loss(self, params: Dict[str, Any], batch: Dict[str, jax.Array],
             rng: jax.Array) -> Tuple[jax.Array, Metrics]:
        del rng
        c = self.config
        tokens = batch["tokens"]
        mask = batch.get("loss_mask")
        mask = (jnp.ones(tokens.shape, jnp.float32) if mask is None
                else mask.astype(jnp.float32))
        x, counters = self._forward_trunk(params, tokens)
        with jax.named_scope("head_loss"):
            loss, accuracy, n_tok = self._shifted_loss(
                params, x, params["norm_f"], tokens, mask, 1)
        metrics = {"accuracy": accuracy, "tokens": n_tok}
        if c.num_nextn_predict_layers:
            x, layer_counters = self._mtp_trunk(params, x, tokens)
            counters = counters + layer_counters
            with _scopes("head_loss", "mtp"):
                mtp_loss, _, _ = self._shifted_loss(
                    params, x, params["mtp"]["norm_f"], tokens, mask, 2)
                loss = loss + c.mtp_loss_weight * mtp_loss
            metrics["mtp_loss"] = mtp_loss
        counters = counters / (c.n_expert_layers + c.num_nextn_predict_layers)
        return loss, {
            "loss": loss, **metrics,
            "moe_held_rows_per_token": counters[0],
            "moe_load_max_over_mean": counters[1],
        }
