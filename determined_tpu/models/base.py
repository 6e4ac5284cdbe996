"""Model interface for the trainer layer.

The reference's trial APIs make the user subclass a framework-specific Trial
(PyTorchTrial `harness/determined/pytorch/_pytorch_trial.py:1385`) whose
methods hand the controller a model, optimizer, and per-batch train/eval
functions. The TPU-native equivalent is purely functional: a `Model` bundles

- ``init(rng) -> params``                    (pure pytree construction)
- ``logical_axes() -> pytree``               (same structure as params; each
  leaf a tuple of logical axis names consumed by
  determined_tpu.parallel.sharding rules — this replaces DeepSpeed topology
  config as the way parallelism attaches to a model)
- ``loss(params, batch, rng) -> (loss, metrics)``  (differentiable)
- ``eval_metrics(params, batch) -> metrics``       (jit-able, no rng)

Models never talk to devices, meshes, or optimizers; the Trainer owns those.
"""
from __future__ import annotations

import abc
from typing import Any, Dict, Tuple

import jax

Params = Any
Batch = Any
Metrics = Dict[str, jax.Array]

#: The `jax.named_scope`s a train step opens, each once where its work is
#: written (`models/gpt.py`: `_embed`, `_attn_half`, `_mlp_half`, `_head` and
#: the loss after it; `Trainer._build_step_fn`: the update), so a profiler
#: capture splits the step's device time by them whatever the layer loop,
#: the remat policy or the mesh. `benchmark/scopes.json` holds the same names.
STEP_SCOPES = ("embed", "attn", "mlp", "head_loss", "optimizer")
#: Scopes INSIDE those, opened by the layers that only some models have
#: (`models/qwen3_next.py`, `models/moe.py`, `ops/gated_delta.py`): `gdn`
#: (the whole gated-delta mixer) and `gated_attn` lie inside `attn`,
#: `gdn_scan` (the chunked rule alone: its batched half and, on a TPU, the
#: kernels `gdn_recurrence_fwd` / `gdn_recurrence_bwd`) inside `gdn`;
#: `moe_route` (router, top-k, sort, the row permutations), `moe_experts`
#: (the grouped matmuls) and `moe_shared` inside `mlp`; `mla` (`models/glm4_moe_lite.py`: the
#: latent-attention mixer but its flash kernels) inside `attn`, and `mtp`
#: (the multi-token-prediction module: its projection inside `embed`, its
#: block inside `attn` and `mlp`, its head pass and loss inside
#: `head_loss`). `benchmark/lm_scopes.json` holds the first six names,
#: `benchmark/mla_scopes.json` the last two.
INNER_SCOPES = (
    "gdn", "gdn_scan", "gated_attn", "moe_route", "moe_experts", "moe_shared",
    "mla", "mtp",
)


class Model(abc.ABC):
    #: What a batch holds, for whoever makes synthetic ones
    #: (`exec/builtin_trials.py`): "tokens" for {"tokens": int32 [B, S]},
    #: or an image's (height, width, channels) for {"image", "label"}.
    input_contract: Any = (28, 28, 1)

    @abc.abstractmethod
    def init(self, rng: jax.Array) -> Params:
        """Build the initial parameter pytree."""

    @abc.abstractmethod
    def logical_axes(self) -> Any:
        """Pytree matching init()'s structure: tuples of logical axis names."""

    @abc.abstractmethod
    def loss(self, params: Params, batch: Batch, rng: jax.Array) -> Tuple[jax.Array, Metrics]:
        """Scalar training loss + auxiliary metrics for one batch."""

    def eval_metrics(self, params: Params, batch: Batch) -> Metrics:
        """Validation metrics for one batch; default reuses loss()."""
        loss, metrics = self.loss(params, batch, jax.random.PRNGKey(0))
        return dict(metrics, loss=loss)
