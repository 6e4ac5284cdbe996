"""HuggingFace integration: Flax transformers models as platform trials.

Rebuild of the reference's model_hub HF adapter + DetCallback
(`model_hub/model_hub/huggingface/_trial.py`,
`harness/determined/transformers/_hf_callback.py:14`) for the JAX stack:
any FlaxAutoModelForCausalLM architecture becomes a `Model` the Trainer can
shard and drive — config-built (offline, random init) for pretraining, or
`from_pretrained` where weights are available locally.

hparams (via HFTrial):
  hf_model_type: "gpt2" | "opt" | ... (transformers model_type)
  hf_config:     dict of config overrides (n_layer, n_embd, ...)
  lr:            adamw learning rate
  batch_size / seq_len: synthetic-data shape (or use your own trial)
"""
from __future__ import annotations

from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from determined_tpu.models.base import Metrics, Model
from determined_tpu.trainer import JAXTrial


class HFFlaxModel(Model):
    """Wrap a Flax transformers causal-LM module as a platform Model."""

    def __init__(
        self,
        model_type: str = "gpt2",
        config_overrides: Optional[Dict[str, Any]] = None,
        dtype: Any = jnp.bfloat16,
        mesh=None,
    ) -> None:
        from transformers import AutoConfig, FlaxAutoModelForCausalLM

        self.config = AutoConfig.for_model(model_type, **(config_overrides or {}))
        # _do_init=False: pure-functional mode — params come from init().
        self._module = FlaxAutoModelForCausalLM.from_config(
            self.config, dtype=dtype, _do_init=False
        )
        self.mesh = mesh

    def init(self, rng: jax.Array):
        shape = (1, int(getattr(self.config, "n_positions", 128)))
        return self._module.init_weights(rng, shape)

    def logical_axes(self):
        """Default FSDP-style annotation: shard every >=2D weight's largest
        dim over fsdp. HF flax trees are arbitrary; this keeps ZeRO-style
        memory scaling without a per-architecture partition table. Dims not
        divisible by the mesh's fsdp axis (e.g. vocab 50257) stay replicated
        — an indivisible PartitionSpec would fail at device_put."""
        abstract = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        fsdp = int(self.mesh.shape.get("fsdp", 1)) if self.mesh is not None else 1

        def annotate(leaf):
            if leaf.ndim < 2:
                return (None,) * leaf.ndim
            largest = int(np.argmax(leaf.shape))
            if fsdp > 1 and leaf.shape[largest] % fsdp != 0:
                return (None,) * leaf.ndim
            return tuple(
                "embed" if i == largest else None for i in range(leaf.ndim)
            )

        return jax.tree.map(annotate, abstract)

    def apply(self, params, tokens: jax.Array) -> jax.Array:
        return self._module(input_ids=tokens, params=params, train=False).logits

    def loss(self, params, batch, rng) -> Tuple[jax.Array, Metrics]:
        del rng
        tokens = batch["tokens"]
        logits = self.apply(params, tokens).astype(jnp.float32)
        logits = logits[:, :-1]
        targets = tokens[:, 1:]
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1).squeeze(-1)
        loss = jnp.mean(lse - tgt)
        acc = jnp.mean((jnp.argmax(logits, -1) == targets).astype(jnp.float32))
        return loss, {"loss": loss, "accuracy": acc}


class HFFlaxClassifier(Model):
    """Flax transformers sequence classifier as a platform Model — the
    BERT-fine-tune rung of the platform ladder (mnist → cifar →
    **BERT fine-tune** → GPT-2 dtrain → GPT-NeoX FSDP). Config-built
    (random init, offline) or from_pretrained where weights are local.

    Batches: {"tokens": int32 [B, S], "label": int32 [B]}.
    """

    def __init__(
        self,
        model_type: str = "bert",
        config_overrides: Optional[Dict[str, Any]] = None,
        num_labels: int = 2,
        dtype: Any = jnp.bfloat16,
        mesh=None,
    ) -> None:
        from transformers import (
            AutoConfig,
            FlaxAutoModelForSequenceClassification,
        )

        self.config = AutoConfig.for_model(
            model_type, num_labels=num_labels, **(config_overrides or {})
        )
        self._module = FlaxAutoModelForSequenceClassification.from_config(
            self.config, dtype=dtype, _do_init=False
        )
        self.mesh = mesh

    def init(self, rng: jax.Array):
        shape = (1, int(getattr(self.config, "max_position_embeddings", 128)))
        return self._module.init_weights(rng, shape)

    # Same generic FSDP annotation as the causal-LM wrapper.
    logical_axes = HFFlaxModel.logical_axes

    def apply(self, params, tokens: jax.Array) -> jax.Array:
        return self._module(
            input_ids=tokens, params=params, train=False
        ).logits

    @staticmethod
    def _metrics(logits: jax.Array, labels: jax.Array) -> Metrics:
        """Shared train/eval metric math — one place to fix (masking,
        smoothing) so the two paths can't diverge."""
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, labels[..., None], axis=-1
        ).squeeze(-1)
        loss = jnp.mean(lse - tgt)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return {"loss": loss, "accuracy": acc}

    def loss(self, params, batch, rng) -> Tuple[jax.Array, Metrics]:
        logits = self._module(
            input_ids=batch["tokens"], params=params, dropout_rng=rng,
            train=True,
        ).logits
        metrics = self._metrics(logits, batch["label"])
        return metrics["loss"], metrics

    def eval_metrics(self, params, batch) -> Metrics:
        return self._metrics(
            self.apply(params, batch["tokens"]), batch["label"]
        )


class HFClassifierTrial(JAXTrial):
    """BERT-class fine-tuning trial (synthetic separable stream by default;
    point `build_training_data` at your tokenized dataset for real work).

    hparams: hf_model_type ("bert"), hf_config overrides, num_labels,
    batch_size, seq_len, lr.
    """

    def build_model(self, mesh):
        return HFFlaxClassifier(
            model_type=self.hparams.get("hf_model_type", "bert"),
            config_overrides=self.hparams.get("hf_config", {}),
            num_labels=int(self.hparams.get("num_labels", 2)),
            mesh=mesh,
        )

    def build_optimizer(self):
        return optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(float(self.hparams.get("lr", 5e-5))),
        )

    def _stream(self, seed: int):
        b = int(self.hparams.get("batch_size", 8))
        s = int(self.hparams.get("seq_len", 64))
        vocab = int(self.hparams.get("hf_config", {}).get("vocab_size", 1024))
        n_labels = int(self.hparams.get("num_labels", 2))
        rng = np.random.default_rng(seed)

        def gen():
            while True:
                label = rng.integers(0, n_labels, (b,)).astype(np.int32)
                toks = rng.integers(2, vocab, (b, s)).astype(np.int32)
                # learnable signal: the first token encodes the class
                toks[:, 0] = 2 + (label % max(1, vocab - 2))  # collision-free for
                # any num_labels < vocab-2 (body tokens start at 2 too,
                # but position 0 deterministically encodes the class)
                yield {"tokens": toks, "label": label}

        return gen()

    def build_training_data(self):
        return self._stream(seed=0)

    def build_validation_data(self):
        it = iter(self._stream(seed=1))
        return [next(it) for _ in range(2)]


class HFTrial(JAXTrial):
    """Plug-and-play trial for HF causal LMs on synthetic or token-shard data."""

    def build_model(self, mesh):
        return HFFlaxModel(
            model_type=self.hparams.get("hf_model_type", "gpt2"),
            config_overrides=self.hparams.get("hf_config", {}),
            mesh=mesh,
        )

    def build_optimizer(self):
        return optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(float(self.hparams.get("lr", 3e-4))),
        )

    def _vocab(self) -> int:
        return int(self.hparams.get("hf_config", {}).get("vocab_size", 50257))

    def _shape(self) -> Tuple[int, int]:
        return (
            int(self.hparams.get("batch_size", 8)),
            int(self.hparams.get("seq_len", 128)),
        )

    def _dataset(self, seed: int):
        from determined_tpu.data import lm_dataset

        b, s = self._shape()
        return lm_dataset(
            self.hparams.get("token_shards"), b, s, self._vocab(), seed=seed
        )

    def build_training_data(self) -> Iterator[Dict[str, Any]]:
        return self._dataset(seed=0)

    def build_validation_data(self):
        # Same source as training (held-out seed): the searcher metric must
        # reflect real data, not synthetic noise.
        it = iter(self._dataset(seed=1))
        return [next(it) for _ in range(2)]
