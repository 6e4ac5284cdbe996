"""GPT: flagship decoder-only transformer, TPU-first.

Capability parity target: the reference's GPT-2 recipes
(`examples/hf_trainer_api/hf_language_modeling`, DeepSpeed
`examples/deepspeed/gpt_neox`) — but built the TPU way rather than wrapping
a torch model:

- parameters are a plain pytree with *logical axis* annotations
  (determined_tpu.parallel.sharding): one rule table flips the model between
  pure DP, FSDP/ZeRO ("embed"→fsdp), Megatron TP ("heads"/"mlp"/"vocab"→
  tensor) and sequence parallelism ("sequence"→context) with zero model
  changes — this replaces the reference's DeepSpeed ZeRO/"slice"/pipeline
  config surface (pytorch/deepspeed/_mpu.py).
- blocks are stacked along a leading `layers` axis and applied either
  unrolled (default up to 24 layers: XLA keeps backward residuals live
  instead of stashing them into [L, ...] buffers) or with `lax.scan` (one
  compiled block program regardless of depth; ASHA searches re-use the
  compilation cache across rungs) — the `layer_loop` knob.
- attention dispatches to the Pallas flash kernel or ring attention via
  determined_tpu.models.attention; matmuls run in bfloat16 with fp32 master
  params and fp32 layernorm/softmax.
- `jax.checkpoint` (rematerialization) per block trades MXU FLOPs for HBM.

All matmul dims are kept multiples of 128 in the standard configs so XLA
tiles them onto the MXU without padding.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from determined_tpu.models import attention as attn_mod
from determined_tpu.models.base import Metrics, Model
from determined_tpu.ops.flash_attention import fit_block, flash_attention


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # GPT-2's 50257 padded up to a multiple of 128
    n_layers: int = 12
    n_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    seq_len: int = 1024
    dtype: Any = jnp.bfloat16          # compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32     # master params
    tie_embeddings: bool = True
    remat: bool = True
    # Keep attention OUTSIDE the remat boundary: flash attention is a
    # custom_vjp whose residuals (q/k/v/o/lse) are rebuilt by re-running the
    # whole forward kernel when rematted — saving them (~60MB/layer at the
    # bench shapes) is far cheaper than the recompute (~8ms/step).
    remat_attention: bool = False
    # How the (non-pipelined) trunk iterates its layer stack:
    #   "scan"   — lax.scan over stacked [L, ...] weights: one compiled
    #              block regardless of depth (compile-time win; the original
    #              default), but every residual the backward needs is saved
    #              by dynamic-update-slice into [L, ...] stacked buffers and
    #              re-read by dynamic-slice — pure HBM traffic.
    #   "unroll" — a Python loop over per-layer weight slices: XLA sees L
    #              independent blocks, keeps residuals as plain live values
    #              (no DUS stash), and fuses across block boundaries.
    #              Measured on v5e GPT-2-small b16: 52.5% MFU vs 43.4% under
    #              scan (+21% tokens/s); profile showed ~25 ms/step of
    #              bitcast_dynamic-update-slice fusions gone. Program size
    #              and compile time grow ~linearly with L.
    #   "auto"   — "unroll" for stacks up to 24 layers at sequence lengths
    #              up to 16k; "scan" for deeper models (compile time /
    #              program size) and for longer sequences, where it ALSO
    #              remats attention (a 12-layer unrolled program at seq
    #              32k fails TPU compilation outright — measured on v5e —
    #              while scan + rematted attention compiles and trains at
    #              37.1% MFU; the flash residuals the split-remat saves
    #              scale with S).
    layer_loop: str = "auto"
    attn_impl: str = "auto"            # see models.attention
    # Flash kernel tile sizes, fitted down to the sequence where it is
    # shorter. A tile equal to the sequence (1024 at GPT-2's context) is
    # what selects the monolithic kernels, the ones both training cells
    # run (ops/flash_attention.py `_mono_ok`; ROADMAP D3).
    flash_block_q: int = 1024
    flash_block_k: int = 1024
    # Replace the constants above with a measurement: probe a small
    # candidate set (ops/flash_autotune.py) at this config's exact
    # attention shapes ONCE at model-build time (outside jit; the winner is
    # cached on disk per device kind / jax version / shape / mask mode).
    # Off by default and on in no cell of the benchmark (ROADMAP D3);
    # off-TPU it is a no-op.
    flash_autotune: bool = False
    # Sliding-window attention: position p attends (p − attn_window, p].
    # None = full causal. The flash kernels skip out-of-band blocks
    # (compute AND DMA) and ring attention stops rotating K/V past the
    # window's reach — O(S·W) attention instead of O(S²).
    attn_window: Optional[int] = None
    z_loss: float = 1e-4               # logit-norm regularizer (stability)
    # Chunked cross-entropy (ops/fused_cross_entropy.py): stream vocab
    # chunks through one unrolled scan instead of materializing [B, S, V]
    # logits. Measured on v5e GPT-2-small: bytes/step 17→12GB, peak HBM
    # −~5GB, but ~2% SLOWER wall-clock (the backward re-runs the vocab
    # matmul once more and XLA already fuses the dense path well) — so the
    # default is the dense loss, and this flag is the memory lever for
    # configs where activations/logits don't fit (long seq, big vocab,
    # larger per-chip batch). Engages when the vocab isn't tensor-sharded
    # and no pipeline/MoE is configured; otherwise falls back to dense.
    fused_loss: bool = False
    # "zigzag": batches arrive pre-shifted in zigzag device order from
    # data/tokens.py (zigzag_ring) — {"tokens","targets","positions"} —
    # and ring attention runs gather-free over the context axis. The
    # contiguous default permutes inside make_ring_attention instead.
    sequence_layout: str = "contiguous"
    # Pipeline parallelism (DeepSpeed PipelineModule analog, TPU-style:
    # stages sharded over the mesh's `pipeline` axis, microbatches advanced
    # by ppermute inside one compiled program — parallel/pipeline.py).
    pipeline_stages: int = 1
    num_microbatches: int = 0          # 0 → 2 × stages (reasonable bubble)
    # "gpipe" fill-drain, or "circular" (interleaved: each device runs
    # pipeline_virtual_stages chunks of layers, round-robin over the ring;
    # bubble shrinks V×; needs microbatches >= stages).
    pipeline_schedule: str = "gpipe"
    pipeline_virtual_stages: int = 2   # V for the circular schedule
    # Mixture of experts (cifar10_moe / DeepSpeed-MoE analog): n_experts > 0
    # replaces every block's MLP with a top-1 (switch) MoE layer; experts
    # shard over the mesh's `expert` axis (GSPMD inserts the all-to-alls).
    n_experts: int = 0
    capacity_factor: float = 1.25

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    def n_params(self) -> int:
        d, f, l, v, s = self.d_model, self.d_ff, self.n_layers, self.vocab_size, self.seq_len
        attn = 4 * d * d + (3 * d + d)
        if self.n_experts:
            e = self.n_experts
            mlp = d * e + e * (d * f + f) + e * (f * d) + d
        else:
            mlp = 2 * d * f + f + d
        per_block = attn + mlp + 4 * d
        embed = v * d + s * d
        head = 0 if self.tie_embeddings else d * v
        return l * per_block + embed + head + 2 * d

    def train_flops_per_token(self) -> float:
        """fwd+bwd FLOPs/token: 6·N_matmul + 12·L·D·S (PaLM convention)."""
        d, f, l, v = self.d_model, self.d_ff, self.n_layers, self.vocab_size
        matmul_params = l * (4 * d * d + 2 * d * f) + d * v
        return 6.0 * matmul_params + 12.0 * l * d * self.seq_len


def small() -> GPTConfig:
    return GPTConfig()  # 124M-class (GPT-2 small)


def medium() -> GPTConfig:
    return GPTConfig(n_layers=24, n_heads=16, d_model=1024, d_ff=4096)


def tiny(seq_len: int = 128) -> GPTConfig:
    """Test-sized config: compiles in seconds on CPU."""
    return GPTConfig(
        vocab_size=256, n_layers=2, n_heads=4, d_model=64, d_ff=256,
        seq_len=seq_len, remat=False,
    )


def _remat_policy():
    """Per-block remat policy: save matmul outputs AND the flash-attention
    kernel output (named in models/attention.py — pallas_call results are
    invisible to the dots policy, and recomputing the attention forward
    inside the backward costs ~8ms/step on the GPT-2 bench)."""
    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names("flash_out"),
    )


def _layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + 1e-5)
    return (y * scale + bias).astype(x.dtype)


# -- the head and the loss sums: every language model's (models/qwen3_next.py
# calls the same three) ------------------------------------------------------
def head_logits(h: jax.Array, w_out: jax.Array) -> jax.Array:
    """The LM head over final-normed hidden states h [B, S, D]; w_out
    [D, V] already in the compute dtype."""
    return jnp.einsum("bsd,dv->bsv", h, w_out)


def aligned_token_sums(
    logits: jax.Array, targets: jax.Array, mask: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Objective SUMS (nll, z, correct, n) over fp32 logits ALIGNED with
    targets (position i predicts targets[i]) — the elementwise core
    shared by the classic shifted path, the 1F1B objective, and the
    pre-shifted zigzag-layout path."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    ).squeeze(-1)
    nll_sum = jnp.sum((lse - target_logit) * mask)
    z_sum = jnp.sum(jnp.square(lse) * mask)
    acc_sum = jnp.sum((jnp.argmax(logits, -1) == targets) * mask)
    return nll_sum, z_sum, acc_sum, jnp.sum(mask)


def next_token_sums(
    logits: jax.Array, tokens: jax.Array, mask: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Classic in-model shift: position i predicts token i+1."""
    return aligned_token_sums(logits[:, :-1], tokens[:, 1:], mask[:, 1:])


class GPT(Model):
    """Decoder-only LM. batch = {"tokens": int32 [B, S]} (next-token loss),
    optional "loss_mask" [B, S] (1.0 = count this target position)."""

    input_contract = "tokens"

    def __init__(self, config: GPTConfig, mesh: Optional[Mesh] = None) -> None:
        self.config = config
        self.mesh = mesh
        # (block_q, block_k): the config values, or the autotuner's probed
        # winner (flash_autotune). Resolved EAGERLY here because the probe
        # runs real device work, which must not happen mid-trace when the
        # train step first calls into attention — model build
        # (trial.build_model) is always outside jit.
        self._resolved_flash_blocks: Optional[Tuple[int, int]] = None
        if config.flash_autotune:
            self._flash_blocks()

    def _flash_blocks(self) -> Tuple[int, int]:
        if self._resolved_flash_blocks is None:
            c = self.config
            if c.flash_autotune:
                from determined_tpu.ops.flash_autotune import (
                    tune_flash_blocks,
                )

                ctx = tp = 1
                if self.mesh is not None:
                    ctx = self.mesh.shape.get("context", 1)
                    tp = self.mesh.shape.get("tensor", 1)
                # Probe the PER-DEVICE kernel shapes: a sharded context
                # axis gives each hop the LOCAL chunk (or half-chunk),
                # and a sharded tensor axis gives each device
                # n_heads/tensor heads — timing the full-head grid would
                # rank candidates on a 'tp'-times-larger problem than the
                # kernel that actually runs.
                s_local = max(c.seq_len // max(ctx, 1), 1)
                h_local = max(c.n_heads // max(tp, 1), 1)
                self._resolved_flash_blocks = tune_flash_blocks(
                    s_q=s_local, n_heads=h_local, head_dim=c.head_dim,
                    dtype=c.dtype, causal=True, window=c.attn_window,
                    want_q=c.flash_block_q, want_k=c.flash_block_k,
                )
            else:
                self._resolved_flash_blocks = (
                    c.flash_block_q, c.flash_block_k
                )
        return self._resolved_flash_blocks

    # -- params ------------------------------------------------------------
    def init(self, rng: jax.Array) -> Dict[str, Any]:
        c = self.config
        d, f, h, hd, l = c.d_model, c.d_ff, c.n_heads, c.head_dim, c.n_layers
        keys = jax.random.split(rng, 8)
        init = jax.nn.initializers.normal(0.02)
        # GPT-2 residual-projection scaling: std/sqrt(2L).
        res_init = jax.nn.initializers.normal(0.02 / (2 * l) ** 0.5)
        pd = c.param_dtype
        blocks: Dict[str, Any] = {
            "ln1_scale": jnp.ones((l, d), pd),
            "ln1_bias": jnp.zeros((l, d), pd),
            "wqkv": init(keys[2], (l, d, 3, h, hd), pd),
            "bqkv": jnp.zeros((l, 3, h, hd), pd),
            "wo": res_init(keys[3], (l, h, hd, d), pd),
            "bo": jnp.zeros((l, d), pd),
            "ln2_scale": jnp.ones((l, d), pd),
            "ln2_bias": jnp.zeros((l, d), pd),
        }
        if c.n_experts:
            e = c.n_experts
            blocks.update(
                router=init(keys[4], (l, d, e), pd),
                we_in=init(keys[5], (l, e, d, f), pd),
                be_in=jnp.zeros((l, e, f), pd),
                we_out=res_init(keys[7], (l, e, f, d), pd),
                bo_mlp=jnp.zeros((l, d), pd),
            )
        else:
            blocks.update(
                wi=init(keys[4], (l, d, f), pd),
                bi=jnp.zeros((l, f), pd),
                wo_mlp=res_init(keys[5], (l, f, d), pd),
                bo_mlp=jnp.zeros((l, d), pd),
            )
        params: Dict[str, Any] = {
            "tok_embed": init(keys[0], (c.vocab_size, d), pd),
            "pos_embed": init(keys[1], (c.seq_len, d), pd),
            "blocks": blocks,
            "lnf_scale": jnp.ones((d,), pd),
            "lnf_bias": jnp.zeros((d,), pd),
        }
        if not c.tie_embeddings:
            params["head"] = init(keys[6], (d, c.vocab_size), pd)
        return params

    def logical_axes(self) -> Dict[str, Any]:
        c = self.config
        blocks: Dict[str, Any] = {
            "ln1_scale": ("layers", "norm"),
            "ln1_bias": ("layers", "norm"),
            "wqkv": ("layers", "embed", None, "heads", "head_dim"),
            "bqkv": ("layers", None, "heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
            "bo": ("layers", "norm"),
            "ln2_scale": ("layers", "norm"),
            "ln2_bias": ("layers", "norm"),
        }
        if c.n_experts:
            blocks.update(
                router=("layers", "embed", None),
                we_in=("layers", "expert", "embed", "mlp"),
                be_in=("layers", "expert", "mlp"),
                we_out=("layers", "expert", "mlp", "embed"),
                bo_mlp=("layers", "norm"),
            )
        else:
            blocks.update(
                wi=("layers", "embed", "mlp"),
                bi=("layers", "mlp"),
                wo_mlp=("layers", "mlp", "embed"),
                bo_mlp=("layers", "norm"),
            )
        axes: Dict[str, Any] = {
            "tok_embed": ("vocab", "embed"),
            "pos_embed": (None, "embed"),
            "blocks": blocks,
            "lnf_scale": ("norm",),
            "lnf_bias": ("norm",),
        }
        if not c.tie_embeddings:
            axes["head"] = ("embed", "vocab")
        return axes

    # -- forward -----------------------------------------------------------
    def _constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def _moe_mlp(
        self, h: jax.Array, blk: Dict[str, jax.Array], manual: bool
    ) -> Tuple[jax.Array, jax.Array]:
        """Top-1 (switch) MoE: returns (output, load-balance aux loss).

        Dispatch is the standard capacity-bucketed einsum form: tokens route
        to [E, C, D] buckets; with `we_in`/`we_out` sharded over the expert
        mesh axis GSPMD lowers the dispatch/combine einsums to all-to-alls
        over ICI (SURVEY.md §2.5 EP row).
        """
        c = self.config
        b, s, d = h.shape
        e = c.n_experts
        t = b * s
        cap = max(1, int(c.capacity_factor * t / e))
        x = h.reshape(t, d)

        gates = jax.nn.softmax(
            jnp.einsum("td,de->te", x, blk["router"].astype(c.dtype)).astype(
                jnp.float32
            )
        )  # [T, E] fp32: routing decisions must not round in bf16
        idx = jnp.argmax(gates, axis=-1)
        gate = jnp.max(gates, axis=-1)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # [T, E]
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # position in expert
        within = pos < cap
        dispatch = jnp.einsum(
            "te,tec->tec", onehot * within,
            jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32),
        )  # [T, E, C]

        xe = jnp.einsum("tec,td->ecd", dispatch.astype(c.dtype), x)
        if not manual:
            xe = self._constrain(xe, P("expert", None, None))
        he = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", xe, blk["we_in"].astype(c.dtype))
            + blk["be_in"].astype(c.dtype)[:, None, :]
        )
        ye = jnp.einsum("ecf,efd->ecd", he, blk["we_out"].astype(c.dtype))
        if not manual:
            ye = self._constrain(ye, P("expert", None, None))
        combine = dispatch * gate[:, None, None]
        y = jnp.einsum("tec,ecd->td", combine.astype(c.dtype), ye)
        y = y + blk["bo_mlp"].astype(c.dtype)

        # Switch-transformer load-balance loss: E * Σ_e fraction_tokens_e ·
        # mean_gate_e — pushes the router toward uniform expert usage.
        frac = jnp.mean(onehot, axis=0)
        mean_gate = jnp.mean(gates, axis=0)
        aux = e * jnp.sum(frac * mean_gate)
        return y.reshape(b, s, d), aux

    def _attend(
        self, *, manual: bool = False,
        segment_ids: Optional[jax.Array] = None,
    ):
        """Training's `attend` for `_attn_half`: the dispatcher of
        models/attention.py over this config's implementation, tiles,
        layout and window. `manual` = inside a shard_map manual region
        (a pipeline stage), where the dispatcher is `attention_manual`
        and takes the three slices."""
        c = self.config
        block_q, block_k = self._flash_blocks()
        how = dict(
            mesh=self.mesh, causal=True, impl=c.attn_impl, block_q=block_q,
            block_k=block_k, layout=c.sequence_layout, window=c.attn_window,
        )
        if not manual:
            return functools.partial(
                attn_mod.attention_qkv, segment_ids=segment_ids, **how
            )

        def attend(qkv):
            with jax.named_scope("attn"):
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            return attn_mod.attention_manual(q, k, v, **how)

        return attend

    def _block(
        self, x: jax.Array, blk: Dict[str, jax.Array], attend, *,
        manual: bool = False,
    ) -> Tuple[jax.Array, jax.Array]:
        """One transformer block → (x, moe_aux). `manual` = running inside a
        shard_map manual region (pipeline stage): no sharding constraints, no
        nested shard_map (`attention_manual`)."""
        x = self._attn_half(x, blk, attend, manual=manual)
        return self._mlp_half(x, blk, manual=manual)

    def _attn_half(
        self, x: jax.Array, blk: Dict[str, jax.Array], attend, *,
        manual: bool = False,
    ) -> jax.Array:
        """LayerNorm → fused QKV projection → `attend` → output projection
        → residual: the one attention half, for training and serving alike.
        `attend(qkv) -> o` is the attention between the projections, qkv
        [B, S, 3, H, D] the fused projection, o [B, S, H, D]: training's
        is `_attend`, each serving entry point brings its own (which is
        also where it takes the layer's K/V).

        The `attn` scope is opened around the projections and closed
        around the attention call between them: a Pallas kernel's name in
        a trace is the innermost component of its name stack, and the
        flash kernels keep the ones they have under no scope
        (`ops/flash_attention.py`)."""
        c = self.config
        with jax.named_scope("attn"):
            h = _layernorm(x, blk["ln1_scale"], blk["ln1_bias"])
            qkv = (
                jnp.einsum("bsd,dthk->bsthk", h, blk["wqkv"].astype(c.dtype))
                + blk["bqkv"].astype(c.dtype)
            )
        o = attend(qkv)
        with jax.named_scope("attn"):
            o = jnp.einsum("bshk,hkd->bsd", o, blk["wo"].astype(c.dtype))
            o = o + blk["bo"].astype(c.dtype)
            x = x + o
            if not manual:
                x = self._constrain(x, P(("data", "fsdp"), "context", None))
        return x

    @jax.named_scope("mlp")
    def _mlp_half(
        self, x: jax.Array, blk: Dict[str, jax.Array], *, manual: bool = False
    ) -> Tuple[jax.Array, jax.Array]:
        c = self.config
        act_spec = P(("data", "fsdp"), "context", None)

        h = _layernorm(x, blk["ln2_scale"], blk["ln2_bias"])
        if c.n_experts:
            m, aux = self._moe_mlp(h, blk, manual)
        else:
            m = jnp.einsum("bsd,df->bsf", h, blk["wi"].astype(c.dtype))
            m = jax.nn.gelu(m + blk["bi"].astype(c.dtype))
            m = jnp.einsum("bsf,fd->bsd", m, blk["wo_mlp"].astype(c.dtype))
            m = m + blk["bo_mlp"].astype(c.dtype)
            aux = jnp.zeros((), jnp.float32)
        x = x + m
        if not manual:
            x = self._constrain(x, act_spec)
        return x, aux

    def _embed_raw(
        self,
        tok_embed: jax.Array,
        pos_embed: jax.Array,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Embedding math shared by the GSPMD path and the 1F1B stage-0
        producer (no sharding constraints). `positions` [S]: explicit
        logical positions for permuted (zigzag) sequence layouts."""
        c = self.config
        x = tok_embed.astype(c.dtype)[tokens]
        pe = pos_embed.astype(c.dtype)
        if positions is not None:
            return x + pe[positions]
        return x + pe[: tokens.shape[1]]

    def _head_raw(
        self,
        lnf_scale: jax.Array,
        lnf_bias: jax.Array,
        w_out: jax.Array,
        x: jax.Array,
    ) -> jax.Array:
        """Final layernorm + LM head shared by _head and the 1F1B last-stage
        loss (no sharding constraints); w_out already in compute dtype."""
        return head_logits(_layernorm(x, lnf_scale, lnf_bias), w_out)

    _aligned_token_sums = staticmethod(aligned_token_sums)
    _next_token_sums = staticmethod(next_token_sums)

    def _stage_scan_fn(self):
        """fp32-boundary runner over a stack [k, ...] of blocks — the
        stage_fn for every pipeline schedule (see the fp32 carry note in
        _apply_pipelined)."""
        c = self.config
        block_fn = functools.partial(
            self._block, attend=self._attend(manual=True), manual=True
        )
        if c.remat:
            block_fn = jax.checkpoint(block_fn, policy=_remat_policy())

        def stage_fn(sp, act):
            def body(carry, blk):
                out, _aux = block_fn(carry.astype(c.dtype), blk)
                return out.astype(jnp.float32), None

            out, _ = lax.scan(body, act, sp)
            return out

        return stage_fn

    @jax.named_scope("embed")
    def _embed(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
    ) -> jax.Array:
        c = self.config
        # Lay the lookup out so the gather's output sharding IS the
        # activation sharding: the indices carry the batch/seq mesh axes and
        # the (explicitly all-gathered) table carries none. Left to
        # propagation, GSPMD inherits the table's fsdp/tensor sharding onto
        # the gather output and then pays an involuntary full
        # replicate-then-partition reshard to reach the activation spec
        # (spmd_partitioner warning seen in the r2 multichip dryrun). The
        # table all-gather itself is not a regression — XLA already emitted
        # one to serve the gather.
        tokens = self._constrain(tokens, P(("data", "fsdp"), "context"))
        table = self._constrain(params["tok_embed"].astype(c.dtype), P(None, None))
        pos = self._constrain(params["pos_embed"].astype(c.dtype), P(None, None))
        x = self._embed_raw(table, pos, tokens, positions)
        return self._constrain(x, P(("data", "fsdp"), "context", None))

    @jax.named_scope("head_loss")
    def _head(self, params: Dict[str, Any], x: jax.Array) -> jax.Array:
        c = self.config
        w_out = (
            params["tok_embed"].T if c.tie_embeddings else params["head"]
        ).astype(c.dtype)
        logits = self._head_raw(
            params["lnf_scale"], params["lnf_bias"], w_out, x
        )
        return self._constrain(logits, P(("data", "fsdp"), "context", "tensor"))

    def _forward(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """→ (logits [B, S, V], moe aux loss)."""
        c = self.config
        if segment_ids is not None and c.pipeline_stages > 1:
            raise ValueError(
                "segment_ids (packed sequences) are not supported with "
                "pipeline parallelism yet"
            )
        if c.sequence_layout == "zigzag" and c.pipeline_stages > 1:
            # Zigzag rides the pipeline: embedding happens BEFORE the
            # pipeline shard_map (positions-aware), and the stages run ring
            # attention in zigzag layout over the manual context axis — a
            # SHARDED context axis is therefore mandatory (dense attention
            # over permuted order would be silently wrong).
            assert positions is not None, (
                "sequence_layout='zigzag' needs a zigzag-emitting data "
                "pipeline (data/tokens.py zigzag_ring) supplying positions"
            )
            assert (
                self.mesh is not None
                and self.mesh.shape.get("context", 1) > 1
            ), (
                "sequence_layout='zigzag' + pipeline parallelism requires "
                "a sharded context axis (ring attention in the stages)"
            )
        if c.pipeline_stages > 1:
            if (
                self.mesh is None
                or self.mesh.shape.get("context", 1) == 1
            ):
                # Without a sharded context axis the stages run DENSE
                # causal attention, whose mask assumes index order == time
                # order — and permuted positions can't be validated at
                # trace time. Contiguous ctx==1 pipelines therefore take
                # positions-free batches (aligned targets are still fine).
                assert positions is None, (
                    "explicit positions with a context-unsharded pipeline "
                    "would silently break the dense causal mask; drop "
                    "'positions' (contiguous data) or shard the context "
                    "axis (ring attention understands permuted layouts)"
                )
            return self._apply_pipelined(params, tokens, positions)

        hidden = self._forward_trunk(params, tokens, positions, segment_ids)
        return self._head(params, hidden[0]), hidden[1]

    def _forward_trunk(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Embed + blocks → (pre-final-layernorm [B, S, D] compute dtype,
        moe_aux). Consumers apply lnf themselves: _head via _head_raw, the
        chunked loss explicitly."""
        c = self.config
        if c.sequence_layout == "zigzag":
            # Guard here, not only in _forward: the chunked-loss path calls
            # the trunk directly and must enforce the same data contract.
            assert positions is not None, (
                "sequence_layout='zigzag' needs a zigzag-emitting data "
                "pipeline (data/tokens.py zigzag_ring) supplying positions"
            )
        x = self._embed(params, tokens, positions)
        # Effective remat_attention: the attention-outside-remat split is
        # the throughput winner at bench sequence lengths, but its saved
        # flash residuals scale with S — at 32k the only configuration
        # measured to compile AND train on v5e is scan + rematted
        # attention, so "auto" flips this knob together with the loop
        # style (the two halves of the same long-sequence regime).
        remat_attn = c.remat_attention or (
            c.layer_loop == "auto" and c.seq_len > 16384
        )
        attend = self._attend(segment_ids=segment_ids)
        if c.remat and not remat_attn:
            mlp_fn = jax.checkpoint(
                functools.partial(self._mlp_half, manual=False),
                policy=_remat_policy(),
            )

            def block_fn(x, blk):
                return mlp_fn(self._attn_half(x, blk, attend), blk)
        else:
            block_fn = functools.partial(self._block, attend=attend)
            if c.remat:
                block_fn = jax.checkpoint(block_fn, policy=_remat_policy())

        unroll = c.layer_loop == "unroll" or (
            c.layer_loop == "auto"
            and c.n_layers <= 24
            and c.seq_len <= 16384
        )
        if unroll:
            # Python loop over per-layer slices: no [L, ...] residual
            # stash (see the layer_loop knob for the measured numbers).
            aux = jnp.zeros((), jnp.float32)
            for i in range(c.n_layers):
                blk = jax.tree_util.tree_map(
                    lambda a, i=i: a[i], params["blocks"]
                )
                x, blk_aux = block_fn(x, blk)
                aux = aux + blk_aux
            return x, aux

        def body(carry, blk):
            x, aux = carry
            x, blk_aux = block_fn(x, blk)
            return (x, aux + blk_aux), None

        (x, aux), _ = lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["blocks"]
        )
        return x, aux

    def _microbatch_split(self, x: jax.Array, m: int):
        """[b, ...] → [m, b/m, ...] microbatches, block-cyclically per
        data×fsdp shard when divisibility allows (comm-free under GSPMD —
        see the layout comment in `_apply_pipelined`). Returns
        (micro, cyclic, shards) so callers can invert the layout."""
        b = x.shape[0]
        mb = b // m
        shards = 1
        if self.mesh is not None:
            shards = self.mesh.shape.get("data", 1) * self.mesh.shape.get(
                "fsdp", 1
            )
        cyclic = shards > 1 and mb % shards == 0
        if cyclic:
            x4 = x.reshape(shards, m, mb // shards, *x.shape[1:])
            return (
                jnp.swapaxes(x4, 0, 1).reshape(m, mb, *x.shape[1:]),
                cyclic,
                shards,
            )
        return x.reshape(m, mb, *x.shape[1:]), cyclic, shards

    def _apply_pipelined(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """GPipe/circular schedule over the mesh's `pipeline` axis
        (parallel/pipeline.py).

        Embedding and LM head stay outside the pipeline (replicated across
        stages); block params reshape [L, ...] → [stages, L/stages, ...] and
        shard over `pipeline`. When the mesh also shards `context`, the
        shard_map goes manual on BOTH axes and each stage runs ring
        attention over its sequence shard (pipeline ppermutes hand-offs,
        context ppermutes K/V — independent rings of the same program);
        remaining axes (data/fsdp/tensor) stay under GSPMD control.
        """
        from jax import shard_map

        from determined_tpu.parallel.pipeline import (
            circular_pipeline_apply,
            pipeline_apply,
            stack_circular_stages,
        )

        c = self.config
        n_stages = c.pipeline_stages
        assert self.mesh is not None, "pipeline parallelism needs a mesh"
        assert self.mesh.shape["pipeline"] == n_stages, (
            f"mesh pipeline axis {self.mesh.shape['pipeline']} != "
            f"config pipeline_stages {n_stages}"
        )
        assert c.n_layers % n_stages == 0
        assert not c.n_experts, "MoE+pipeline composition not supported yet"
        b = tokens.shape[0]
        m = c.num_microbatches or 2 * n_stages
        assert b % m == 0, f"batch {b} not divisible by {m} microbatches"

        x = self._embed(params, tokens, positions)
        # Carries through the pipeline's scan/ppermute stay fp32: bf16
        # loop-carried values under partial-manual shard_map trip an XLA
        # SPMD-partitioner check failure ("invalid binary instruction opcode
        # copy"); compute inside each block still runs in the compute dtype.
        #
        # Block-cyclic microbatching: x's batch dim is contiguously sharded
        # over data×fsdp (device d owns rows [d·b/D, (d+1)·b/D)). A plain
        # reshape(m, mb) hands microbatch j the contiguous rows
        # [j·mb, (j+1)·mb) — a cross-device resharding GSPMD can only
        # realize as a replicate-then-partition copy (the r2 dryrun
        # warning). Splitting per shard instead keeps every row on its
        # device: microbatch j takes rows [j·mb/D, (j+1)·mb/D) of each
        # shard's block, so the reshape+transpose is local and the inverse
        # below restores logits↔tokens alignment exactly.
        mb = b // m
        micro, cyclic, shards = self._microbatch_split(x, m)
        micro = micro.astype(jnp.float32)
        micro = self._constrain(micro, P(None, ("data", "fsdp"), "context", None))

        blocks_scan = self._stage_scan_fn()

        assert c.pipeline_schedule in ("gpipe", "circular", "1f1b"), (
            f"unknown pipeline_schedule {c.pipeline_schedule!r} "
            "(one of: gpipe, circular, 1f1b)"
        )
        # 1F1B is a *training* schedule (loss() runs it via _loss_1f1b);
        # forward-only inference uses the fill-drain layout.
        circular = c.pipeline_schedule == "circular"
        if circular:
            # [L, ...] → [S·V, per, ...] → round-robin [S, V, per, ...]:
            # device d runs global chunks d, d+S, … (interleaved schedule).
            v = c.pipeline_virtual_stages
            assert c.n_layers % (n_stages * v) == 0, (
                f"n_layers {c.n_layers} must divide stages×virtual "
                f"({n_stages}×{v})"
            )
            per_stage = c.n_layers // (n_stages * v)
            global_stages = jax.tree.map(
                lambda leaf: leaf.reshape(
                    n_stages * v, per_stage, *leaf.shape[1:]
                ),
                params["blocks"],
            )
            stage_blocks = stack_circular_stages(global_stages, n_stages)
            apply_fn = circular_pipeline_apply
        else:
            per_stage = c.n_layers // n_stages
            stage_blocks = jax.tree.map(
                lambda leaf: leaf.reshape(n_stages, per_stage, *leaf.shape[1:]),
                params["blocks"],
            )
            apply_fn = pipeline_apply

        def run(sp, mbs):
            sp = jax.tree.map(lambda leaf: leaf[0], sp)  # drop S dim (=1)
            return apply_fn(blocks_scan, sp, mbs)

        ctx = self.mesh.shape.get("context", 1)
        manual_axes = {"pipeline"} | ({"context"} if ctx > 1 else set())
        # With a sharded context axis the microbatches enter seq-sharded
        # (dim 2) and each stage's ring attention owns that axis manually.
        micro_spec = P(None, None, "context", None) if ctx > 1 else P()
        piped = shard_map(
            run,
            mesh=self.mesh,
            in_specs=(
                jax.tree.map(lambda _: P("pipeline"), stage_blocks),
                micro_spec,
            ),
            out_specs=micro_spec,
            axis_names=manual_axes,
            check_vma=False,
        )
        out = piped(stage_blocks, micro)  # [M, mb, S, D] fp32
        if cyclic:
            o4 = out.reshape(m, shards, mb // shards, *out.shape[2:])
            x = jnp.swapaxes(o4, 0, 1).reshape(b, *out.shape[2:])
        else:
            x = out.reshape(b, *out.shape[2:])
        x = self._constrain(
            x, P(("data", "fsdp"), "context", None)
        ).astype(c.dtype)
        return self._head(params, x), jnp.zeros((), jnp.float32)

    def apply(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
    ) -> jax.Array:
        """tokens [B, S] int32 → logits [B, S, V] (compute dtype)."""
        return self._forward(params, tokens, positions, segment_ids)[0]

    # -- serving: kv-cache-aware forward ------------------------------------
    # The generation service (determined_tpu/serving) runs two step shapes,
    # both static so the engine never recompiles as requests come and go:
    # a packed prefill over pack_sequences batches, and a decode over a
    # paged KV pool (one query row per slot, or a draft's worth). Both lean
    # on the flash kernels' masking model — segment_ids isolate packed
    # prompts, and decode runs causal + kv_offset (the bottom-aligned
    # short-q geometry) with segment masking trimming each row's dead
    # cache tail. Every entry point is `_serve` (the trunk: the blocks
    # training runs) around an `attend` of its own, which is also where
    # it takes each layer's K/V.
    def _serve(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        positions: jax.Array,
        attend,
    ) -> jax.Array:
        """Embed at explicit `positions` [B, S], run every block with
        `attend(i, qkv) -> o` as layer i's attention, → logits [B, S, V]
        (compute dtype). No sharding constraints are applied: serving
        replicas are single-device (mesh=None, `_constrain` a no-op)."""
        c = self.config
        if c.pipeline_stages > 1:
            raise ValueError("serving does not support pipeline stages")
        x = self._embed(params, tokens, positions)
        for i in range(c.n_layers):
            blk = jax.tree_util.tree_map(lambda a, i=i: a[i], params["blocks"])
            x = self._attn_half(x, blk, functools.partial(attend, i))
            x, _aux = self._mlp_half(x, blk)
        return self._head(params, x)

    def prefill_kv(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        positions: jax.Array,
        segment_ids: jax.Array,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Packed prefill that also returns every layer's K/V.

        tokens [B, S] int32 — prompts packed back to back per row
        (batch_inference.pack_sequences layout); positions [B, S] int32 —
        each token's position WITHIN its own document (pos_embed index);
        segment_ids [B, S] int32 — 1, 2, ... per document, 0 on padding.

        → (logits [B, S, V] compute dtype,
           k [L, B, S, H, Dh], v [L, B, S, H, Dh] compute dtype).

        The serving engine scatters each document's K/V slice into its
        page-pool pages and samples the first generated token from the
        logits at the document's last prompt position. No sharding
        constraints: serving replicas are single-device (mesh=None).
        """
        c = self.config
        s = tokens.shape[1]
        bq = fit_block(s, c.flash_block_q)
        bk = fit_block(s, c.flash_block_k)
        ks, vs = [], []

        def attend(i, qkv):
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            ks.append(k)
            vs.append(v)
            return flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                segment_ids=segment_ids,
            )

        logits = self._serve(params, tokens, positions, attend)
        return logits, jnp.stack(ks), jnp.stack(vs)

    def prefill_kv_cached(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        positions: jax.Array,
        segment_ids: jax.Array,
        prefix_k: jax.Array,
        prefix_v: jax.Array,
        prefix_seg: jax.Array,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Tail prefill that attends THROUGH an already-cached prefix.

        The prefix-cache hit path: a request whose leading pages matched
        the radix cache computes K/V only for its tail tokens, but those
        tail tokens must still attend to the cached prefix — so each
        layer concatenates the (gathered) cached prefix K/V in front of
        the tail's own and runs the flash kernel in the bottom-aligned
        ``kv_offset`` geometry the decode path already uses.

        tokens [B, S] int32 — ONE document tail per row (rows cannot be
        packed: each has its own prefix buffer); positions [B, S] int32 —
        ABSOLUTE positions (cached_tokens + offset — the pos_embed index
        must match what a full prefill would have used); segment_ids
        [B, S] — 1 on real tail tokens, 0 on padding; prefix_k/prefix_v
        [L, B, Sp, H, Dh] — each row's cached pages gathered contiguous
        (dead tail rows arbitrary); prefix_seg [B, Sp] — 1 on live prefix
        positions, 0 past row's prefix length.

        → (logits [B, S, V], k [L, B, S, H, Dh], v) — K/V of the TAIL
        only (the prefix's K/V already live in the page pool). With
        ``kv_offset = Sp`` query row r sees every (live) prefix key plus
        tail keys ≤ r — exactly the causal mask of the full prompt, so
        greedy streams are identical to the cache-off path.
        """
        c = self.config
        s = tokens.shape[1]
        sp = prefix_k.shape[2]
        bq = fit_block(s, c.flash_block_q)
        bk = fit_block(sp + s, c.flash_block_k)
        kv_seg = jnp.concatenate([prefix_seg, segment_ids], axis=1)
        ks, vs = [], []

        def attend(i, qkv):
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            ks.append(k)
            vs.append(v)
            return flash_attention(
                q,
                jnp.concatenate([prefix_k[i].astype(k.dtype), k], axis=1),
                jnp.concatenate([prefix_v[i].astype(v.dtype), v], axis=1),
                causal=True, kv_offset=sp, block_q=bq, block_k=bk,
                segment_ids=segment_ids, kv_segment_ids=kv_seg,
            )

        logits = self._serve(params, tokens, positions, attend)
        return logits, jnp.stack(ks), jnp.stack(vs)

    def decode_kv(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        lengths: jax.Array,
        q_lens: jax.Array,
        active: jax.Array,
        cache_k: jax.Array,
        cache_v: jax.Array,
        page_table: jax.Array,
        *,
        q_pad: int = 1,
        kernel: str = "gather",
        block_h: Optional[int] = None,
        interpret: bool = False,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One iteration-level decode step over the paged KV cache: Q
        positions per slot scored in ONE step. Q = 1 is the plain
        one-token decode, Q > 1 the draft-verify step of speculative
        decoding; the engine compiles one program for each.

        tokens [B, Q] int32 — row 0 is the token each slot processes
        this iteration (its last committed token: it sits at position
        lengths[b]), followed by the slot's draft (rows 1..q_lens[b]−1,
        at positions lengths[b]+r); rows past q_lens[b] are padding the
        engine ignores. lengths [B] int32 — tokens already cached per
        slot; q_lens [B] int32 — real rows per slot (≥ 1): a plain slot
        rides the verify step with q_lens = 1, so speculating and
        non-speculating slots mix in one iteration with every shape
        static; active [B] bool — live slots; cache_k/cache_v
        [L, n_pages, page_size, H, Dh] — the page pool (page 0 is the
        engine's scratch page); page_table [B, P] int32 — each slot's
        pages in order.

        → (logits [B, Q, V] fp32, cache_k, cache_v): logits[b, r]
        predicts position lengths[b]+r+1 (at Q = 1: the NEXT token), so
        greedy acceptance walks drafts against argmax(logits[:, :-1])
        and the accepted prefix's emissions come straight off the same
        array. ALL Q rows' K/V are written at their positions first
        (live rows through the page table, inactive/dead/pad rows to the
        scratch page, so the scatter stays unconditional): an accepted
        prefix is already committed in the pool, and a rejected tail
        sits at positions past the rewound length — invisible to both
        kernels' masks and overwritten before those positions ever go
        live. Every shape is static in (B, Q, P, pool geometry):
        requests joining/leaving the batch between iterations never
        trigger a recompile.

        Two kernels, one contract (`kernel`):

        - ``"paged"`` — ops/paged_attention.py reads K/V straight out of
          the pool through the page table (scalar-prefetch index_map);
          the per-row bottom-aligned masking (``q_lens``: row r's page
          regimes/masks are the single-token kernel's at length+r) and
          dead-tail trimming live inside the kernel: dead pages cost
          neither DMA nor compute, and NO contiguous [B, S_max, H, Dh]
          buffer ever materializes. `block_h` (heads per grid step)
          comes from ops/flash_autotune.tune_paged_block_h; `interpret`
          runs the kernel in Pallas interpret mode (the CPU parity/test
          path).
        - ``"gather"`` — the fallback: the committed window [B, S_max]
          is gathered contiguous with STRICT segment masking (pos <
          lengths: row 0's token is NOT read from the pool; inactive
          rows carry a q-segment matching nothing) and the Q fresh
          rows' K/V concatenate behind it at ``kv_offset = S_max`` —
          causal over the tail gives row r exactly tail rows ≤ r, i.e.
          positions ≤ lengths[b]+r: the prefill_kv_cached concat
          geometry at decode scale.

        `q_pad` rounds Q up to a lane-friendly row count on TPU (the
        extra rows are dropped before return); above 1 the gather path
        also pads its fresh tail so the keys are whole 128-lane blocks.
        """
        c = self.config
        if kernel not in ("paged", "gather"):
            raise ValueError(
                f"decode_kv kernel must be 'paged' or 'gather', "
                f"got {kernel!r}"
            )
        _n_layers, _n_pages, page_size, h, hd = cache_k.shape
        b, q_n = tokens.shape
        n_page_slots = page_table.shape[1]
        s_max = n_page_slots * page_size
        qpad = max(1, int(q_pad))
        qp = -(-q_n // qpad) * qpad        # Q rounded up to the lane pad
        r = jnp.arange(q_n)
        pos = lengths[:, None] + r[None, :]            # [B, Q]
        live = active[:, None] & (r[None, :] < q_lens[:, None])
        # Write coordinates for every row's K/V; dead and padding rows
        # route to the scratch page so the scatter stays unconditional.
        widx = page_table[
            jnp.arange(b)[:, None],
            jnp.clip(pos // page_size, 0, n_page_slots - 1),
        ]
        widx = jnp.where(live, widx, 0)
        woff = pos % page_size
        if kernel == "gather":
            # STRICT boundary: the committed window ends at lengths−1 —
            # row 0's token (and the draft) ride in the fresh tail, so
            # the just-scattered pool rows are never double-counted.
            kv_seg_win = (
                (jnp.arange(s_max)[None, :] < lengths[:, None])
                & active[:, None]
            ).astype(jnp.int32)  # [B, S_max]
            # On the chip (q_pad > 1) the key axis is cut into whole
            # 128-lane blocks: S_max + Q is never one (8·odd at 128-row
            # pages), so the fresh tail is padded until window + tail is.
            kp = qp if qpad == 1 else -(-(s_max + qp) // 128) * 128 - s_max
            tail_live = (
                (jnp.arange(kp)[None, :] < q_lens[:, None]) & active[:, None]
            )  # [B, kp]
            kv_seg = jnp.concatenate(
                [kv_seg_win, tail_live.astype(jnp.int32)], axis=1
            )
            # live q rows match live keys (id 1); inactive slots and pad
            # rows get ids matching nothing kv-side (never 0 — pad is 0).
            q_seg = jnp.where(tail_live[:, :qp], 1, 2).astype(jnp.int32)
            bq = fit_block(qp, 128)
            bk = fit_block(s_max + kp, c.flash_block_k)
        else:
            from determined_tpu.ops.paged_attention import paged_attention

        def lane_pad(a, rows):
            if rows == q_n:
                return a
            return jnp.concatenate(
                [a, jnp.zeros((b, rows - q_n, h, hd), a.dtype)], axis=1
            )

        def attend(i, qkv):
            nonlocal cache_k, cache_v
            q, k_new, v_new = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            cache_k = cache_k.at[i, widx, woff].set(k_new)
            cache_v = cache_v.at[i, widx, woff].set(v_new)
            if kernel == "paged":
                o = paged_attention(
                    lane_pad(q, qp), cache_k[i], cache_v[i], page_table,
                    lengths, active, q_lens=q_lens, block_h=block_h,
                    interpret=interpret,
                )
            else:
                k_full = cache_k[i][page_table].reshape(b, s_max, h, hd)
                v_full = cache_v[i][page_table].reshape(b, s_max, h, hd)
                o = flash_attention(
                    lane_pad(q, qp),
                    jnp.concatenate([k_full, lane_pad(k_new, kp)], axis=1),
                    jnp.concatenate([v_full, lane_pad(v_new, kp)], axis=1),
                    causal=True, kv_offset=s_max,
                    segment_ids=q_seg, kv_segment_ids=kv_seg,
                    block_q=bq, block_k=bk,
                )
            return o[:, :q_n]

        logits = self._serve(
            params, tokens, jnp.clip(pos, 0, c.seq_len - 1), attend
        )
        return logits.astype(jnp.float32), cache_k, cache_v

    # -- 1F1B training path ------------------------------------------------
    def _loss_1f1b(
        self, params: Dict[str, Any], batch: Dict[str, jax.Array]
    ) -> Tuple[jax.Array, Metrics]:
        """Memory-bounded pipelined training step (schedule="1f1b").

        Embedding and head/loss move INSIDE the pipeline (stage 0 embeds each
        microbatch from its int32 tokens; the last stage computes the
        per-microbatch loss and seeds its backward immediately) so no [M,
        mb, s, d] activation array ever materializes — the residency bound
        is `one_f_one_b_stash_size` = O(S) stage inputs per device, vs
        GPipe's O(M). The schedule itself computes finished gradients
        (parallel/pipeline.py one_f_one_b_grads); a custom_vjp hands them to
        the trainer's jax.grad unchanged. eval reuses this path and simply
        discards the gradients.
        """
        from jax import shard_map
        from determined_tpu.parallel.pipeline import one_f_one_b_grads

        c = self.config
        if batch.get("segment_ids") is not None:
            # Same error (and -O-proof raise) as _forward: silently
            # ignoring the ids would attend across packed documents.
            raise ValueError(
                "segment_ids (packed sequences) are not supported with "
                "pipeline parallelism yet"
            )
        tokens = batch["tokens"]
        targets = batch.get("targets")
        positions = batch.get("positions")
        mask = batch.get("loss_mask")
        b, s = tokens.shape
        n_stages = c.pipeline_stages
        assert self.mesh is not None, "pipeline parallelism needs a mesh"
        assert self.mesh.shape["pipeline"] == n_stages
        assert c.n_layers % n_stages == 0
        assert not c.n_experts, "MoE+pipeline composition not supported yet"
        ctx = self.mesh.shape.get("context", 1)
        aligned = targets is not None
        if ctx > 1 or c.sequence_layout == "zigzag":
            # The in-model shift crosses seq-shard boundaries (and zigzag
            # order entirely): sequence-parallel / zigzag 1F1B requires
            # PRE-SHIFTED batches from the data pipeline.
            assert aligned, (
                "1F1B with a sharded context axis (or zigzag layout) needs "
                "pre-shifted batches: data/tokens.py's zigzag_ring (or an "
                "aligned {'tokens','targets','positions'} stream)"
            )
        if c.sequence_layout == "zigzag":
            assert ctx > 1, (
                "sequence_layout='zigzag' + pipeline needs a sharded "
                "context axis (ring attention in the stages)"
            )
            assert positions is not None
        if ctx == 1:
            # Same dense-causal-mask guard as _forward: permuted positions
            # can't be validated at trace time, so a context-unsharded
            # 1F1B takes positions-free batches.
            assert positions is None, (
                "explicit positions with a context-unsharded pipeline "
                "would silently break the dense causal mask; drop "
                "'positions' or shard the context axis"
            )
        m = c.num_microbatches or 2 * n_stages
        assert b % m == 0, f"batch {b} not divisible by {m} microbatches"
        per_stage = c.n_layers // n_stages

        mask_f = (
            jnp.ones(tokens.shape, jnp.float32)
            if mask is None
            else mask.astype(jnp.float32)
        )
        tok3, _, _ = self._microbatch_split(tokens, m)
        msk3, _, _ = self._microbatch_split(mask_f, m)
        seq_spec = P(None, ("data", "fsdp"), "context")
        tok3 = self._constrain(tok3, seq_spec)
        msk3 = self._constrain(msk3, seq_spec)
        tgt3 = None
        if aligned:
            tgt3, _, _ = self._microbatch_split(targets, m)
            tgt3 = self._constrain(tgt3, seq_spec)

        stage_fn = self._stage_scan_fn()

        @jax.named_scope("embed")
        def emb_fn(ep, tok, pos):
            return self._embed_raw(
                ep["tok_embed"], ep["pos_embed"], tok, pos
            ).astype(jnp.float32)

        @jax.named_scope("head_loss")
        def loss_fn(lp, y, tok, msk):
            """Per-microbatch SUM objective + [nll, z, acc, n] sums —
            the same _head_raw + sums math as the GSPMD path. In aligned
            mode `tok` IS the targets (no shift); with a manual context
            axis the sums are psum'd global so every shard seeds its
            backward with the global objective's cotangent."""
            w_out = (
                lp["tok_embed"].T if c.tie_embeddings else lp["head"]
            ).astype(c.dtype)
            logits = self._head_raw(
                lp["lnf_scale"], lp["lnf_bias"], w_out, y.astype(c.dtype)
            ).astype(jnp.float32)
            if aligned:
                nll_sum, z_sum, acc_sum, n_tok = self._aligned_token_sums(
                    logits, tok, msk
                )
            else:
                nll_sum, z_sum, acc_sum, n_tok = self._next_token_sums(
                    logits, tok, msk
                )
            # The OBJECTIVE stays LOCAL: psum-ing it before the vjp would
            # transpose into a psum of the unit cotangents (each shard's
            # "global" objective re-counts every shard's terms), inflating
            # all gradients by ctx. Local objectives seed local partial
            # grads, and one_f_one_b_grads psums the partials over
            # reduce_axes exactly once. Only the METRIC sums go global.
            obj = nll_sum + c.z_loss * z_sum
            if ctx > 1:
                nll_sum, z_sum, acc_sum, n_tok = (
                    lax.psum(v, "context")
                    for v in (nll_sum, z_sum, acc_sum, n_tok)
                )
            return obj, jnp.stack([nll_sum, z_sum, acc_sum, n_tok])

        def fwd_impl(p):
            stage_blocks = jax.tree.map(
                lambda leaf: leaf.reshape(
                    n_stages, per_stage, *leaf.shape[1:]
                ),
                p["blocks"],
            )
            ep = {"tok_embed": p["tok_embed"], "pos_embed": p["pos_embed"]}
            lp = {"lnf_scale": p["lnf_scale"], "lnf_bias": p["lnf_bias"]}
            if c.tie_embeddings:
                lp["tok_embed"] = p["tok_embed"]
            else:
                lp["head"] = p["head"]

            reduce_axes = ("context",) if ctx > 1 else ()

            def run(sp, tk, mk, tg, pos, ep_, lp_):
                sp = jax.tree.map(lambda leaf: leaf[0], sp)
                return one_f_one_b_grads(
                    stage_fn, sp, emb_fn, ep_, loss_fn, lp_, tk, mk,
                    targets_mb=tg, positions=pos,
                    reduce_axes=reduce_axes,
                )

            stage_spec = jax.tree.map(lambda _: P("pipeline"), stage_blocks)
            manual_axes = {"pipeline"} | ({"context"} if ctx > 1 else set())
            mb_spec = P(None, None, "context") if ctx > 1 else P()
            pos_spec = P("context") if ctx > 1 else P()
            pos_arr = (
                positions if positions is not None
                else jnp.arange(s, dtype=jnp.int32)
            )
            msums, s_g, e_g, l_g = shard_map(
                run,
                mesh=self.mesh,
                in_specs=(
                    stage_spec, mb_spec, mb_spec, mb_spec, pos_spec,
                    P(), P(),
                ),
                out_specs=(P(), stage_spec, P(), P()),
                axis_names=manual_axes,
                check_vma=False,
            )(
                stage_blocks, tok3, msk3,
                tgt3 if tgt3 is not None else tok3,  # unused when not aligned
                pos_arr, ep, lp,
            )

            n = jnp.maximum(msums[3], 1.0)
            loss = msums[0] / n + c.z_loss * msums[1] / n
            metrics = {
                "loss": loss,
                "accuracy": msums[2] / n,
                "tokens": msums[3],
            }
            # The schedule differentiated the per-microbatch SUM objective;
            # the reported loss is sum/n. Gradients are linear in the seed,
            # so scale once here.
            inv_n = 1.0 / n
            grads = {
                "blocks": jax.tree.map(
                    lambda g: g.reshape(c.n_layers, *g.shape[2:]) * inv_n,
                    s_g,
                ),
                "tok_embed": e_g["tok_embed"] * inv_n,
                "pos_embed": e_g["pos_embed"] * inv_n,
                "lnf_scale": l_g["lnf_scale"] * inv_n,
                "lnf_bias": l_g["lnf_bias"] * inv_n,
            }
            if c.tie_embeddings:
                grads["tok_embed"] = (
                    grads["tok_embed"] + l_g["tok_embed"] * inv_n
                )
            else:
                grads["head"] = l_g["head"] * inv_n
            return loss, metrics, grads

        @jax.custom_vjp
        def pipelined(p):
            loss, metrics, _ = fwd_impl(p)
            return loss, metrics

        def pipelined_fwd(p):
            loss, metrics, grads = fwd_impl(p)
            return (loss, metrics), grads

        def pipelined_bwd(grads, cot):
            g_loss, _g_metrics = cot
            return (jax.tree.map(lambda g: g * g_loss, grads),)

        pipelined.defvjp(pipelined_fwd, pipelined_bwd)
        return pipelined(params)

    # -- loss --------------------------------------------------------------
    def loss(
        self, params: Dict[str, Any], batch: Dict[str, jax.Array], rng: jax.Array
    ) -> Tuple[jax.Array, Metrics]:
        del rng  # no dropout in the pretraining configs
        if self.config.pipeline_stages > 1 and (
            self.config.pipeline_schedule == "1f1b"
        ):
            return self._loss_1f1b(params, batch)
        tokens = batch["tokens"]
        targets = batch.get("targets")
        positions = batch.get("positions")
        segment_ids = batch.get("segment_ids")
        mask = batch.get("loss_mask")
        mask = (
            jnp.ones(tokens.shape, jnp.float32)
            if mask is None
            else mask.astype(jnp.float32)
        )
        if segment_ids is not None and targets is None:
            # Packed sequences with the in-model shift: position i−1
            # predicting token i crosses a document boundary wherever the
            # segment id changes at i — mask those predictions out, and
            # drop padding (segment id 0, the pack_sequences convention:
            # pad→pad has equal ids, so the boundary mask alone would
            # score pad predictions). An explicit loss_mask (e.g. from
            # pack_sequences itself) composes multiplicatively.
            # Pre-shifted batches (targets given) carry their own mask
            # from the data pipeline.
            boundary = jnp.concatenate(
                [
                    jnp.ones_like(mask[:, :1]),
                    (segment_ids[:, 1:] == segment_ids[:, :-1]).astype(
                        jnp.float32
                    ),
                ],
                axis=1,
            )
            mask = mask * boundary * (segment_ids != 0)
        c = self.config
        use_fused = (
            c.fused_loss
            and c.pipeline_stages == 1
            and not c.n_experts  # moe_aux handling stays on the dense path
            and (
                self.mesh is None
                or self.mesh.shape.get("tensor", 1) == 1
            )
        )
        if use_fused:
            return self._loss_fused(
                params, tokens, targets, positions, mask, segment_ids
            )
        logits, moe_aux = self._forward(params, tokens, positions, segment_ids)
        with jax.named_scope("head_loss"):
            if targets is not None:
                # Pre-shifted batch (zigzag-layout pipelines, data/tokens.py):
                # position i already predicts targets[i] — no in-model shift.
                nll_sum, z_sum, acc_sum, n_tok = self._aligned_token_sums(
                    logits.astype(jnp.float32), targets, mask
                )
            else:
                # Next-token prediction: position i predicts token i+1 (shift
                # + per-token sums shared with 1F1B via _aligned_token_sums).
                nll_sum, z_sum, acc_sum, n_tok = self._next_token_sums(
                    logits.astype(jnp.float32), tokens, mask
                )
            n = jnp.maximum(n_tok, 1.0)
            loss = nll_sum / n
            if self.config.z_loss:
                loss = loss + self.config.z_loss * z_sum / n
            if self.config.n_experts:
                # 0.01 is the standard switch-transformer aux weight; mean
                # over layers (aux accumulated once per block in the scan).
                loss = loss + 0.01 * moe_aux / self.config.n_layers
            acc = acc_sum / n
        return loss, {"loss": loss, "accuracy": acc, "tokens": n_tok}

    def _loss_fused(
        self, params, tokens, targets, positions, mask, segment_ids=None
    ) -> Tuple[jax.Array, Metrics]:
        """Loss via the chunked cross-entropy (ops/fused_cross_entropy.py):
        identical math to the dense path, ~half the HBM traffic (the [B, S,
        V] logits never materialize)."""
        from determined_tpu.ops.fused_cross_entropy import (
            fused_next_token_sums,
        )

        c = self.config
        x, _moe_aux = self._forward_trunk(
            params, tokens, positions, segment_ids
        )
        with jax.named_scope("head_loss"):
            hidden = _layernorm(x, params["lnf_scale"], params["lnf_bias"])
            w_out = (
                params["tok_embed"].T if c.tie_embeddings else params["head"]
            ).astype(c.dtype)
            if targets is None:
                # classic in-model shift: position i predicts token i+1
                hidden = hidden[:, :-1]
                targets = tokens[:, 1:]
                mask = mask[:, 1:]
            obj, _nll, _z, acc_sum, n_tok = fused_next_token_sums(
                hidden, w_out, targets, mask, z_loss=c.z_loss or 0.0,
            )
            n = jnp.maximum(n_tok, 1.0)
            loss = obj / n
            acc = acc_sum / n
        return loss, {"loss": loss, "accuracy": acc, "tokens": n_tok}

    def eval_metrics(self, params: Dict[str, Any], batch: Dict[str, jax.Array]) -> Metrics:
        loss, metrics = self.loss(params, batch, jax.random.PRNGKey(0))
        return metrics
