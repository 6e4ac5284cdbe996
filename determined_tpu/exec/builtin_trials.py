"""Built-in trials: registry models + synthetic data.

The platform analog of the reference's no-op / pytorch_identity e2e fixtures
(`e2e_tests/tests/fixtures/no_op/model_def.py:19`) plus runnable examples:
an experiment config can point its entrypoint here and select any model
from determined_tpu.models via hyperparameters, with synthetic data —
letting cluster e2e tests and smoke runs work without shipping user code.

hparams:
  model:      registry name (default "mnist-mlp")
  model_kw:   dict passed to the registry constructor
  lr:         adam learning rate (default 1e-3)
  lr_warmup_steps: steps of a linear ramp from 0 to lr, constant after
              (default 0: no schedule, lr from the first step)
  batch_size: global batch (default 16)
  seq_len:    for LM models (default matches model config)
  sleep_s:    per-batch sleep — the "no-op trial" knob for scheduler tests
"""
from __future__ import annotations

import time
from typing import Any, Dict, Iterator

import numpy as np
import optax

from determined_tpu.models import get_model
from determined_tpu.trainer import JAXTrial


class SyntheticTrial(JAXTrial):
    """Any registry model on synthetic data shaped to its input contract."""

    def build_model(self, mesh):
        name = self.hparams.get("model", "mnist-mlp")
        self._model_name = name
        model = get_model(name, mesh=mesh, **self.hparams.get("model_kw", {}))
        self._input_contract = model.input_contract
        return model

    def _contract(self):
        """The model's `input_contract` (models/base.py); asked of a
        model built for the purpose where the trainer has built none
        yet (building allocates nothing)."""
        if not hasattr(self, "_input_contract"):
            self._input_contract = self.build_model(None).input_contract
        return self._input_contract

    def build_optimizer(self):
        lr = float(self.hparams.get("lr", 1e-3))
        warmup = int(self.hparams.get("lr_warmup_steps", 0))
        if warmup > 0:
            # the update of step n (counted from 1) runs at lr * n / warmup
            lr = optax.linear_schedule(lr / warmup, lr, warmup - 1)
        return optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(lr))

    def _batches(self, seed: int) -> Iterator[Dict[str, Any]]:
        rng = np.random.default_rng(seed)
        b = int(self.hparams.get("batch_size", 16))
        sleep_s = float(self.hparams.get("sleep_s", 0.0))
        contract = self._contract()
        while True:
            if sleep_s:
                time.sleep(sleep_s)
            if contract == "tokens":
                s = int(self.hparams.get("seq_len", 128))
                vocab = int(self.hparams.get("vocab_size", 256))
                yield {"tokens": rng.integers(0, vocab, (b, s)).astype(np.int32)}
            else:
                yield {
                    "image": rng.normal(size=(b, *contract)).astype(np.float32),
                    "label": rng.integers(0, 10, (b,)).astype(np.int32),
                }

    def build_training_data(self):
        return self._batches(0)

    def build_validation_data(self):
        it = self._batches(1)
        return [next(it) for _ in range(2)]


class CrashingTrial(SyntheticTrial):
    """Fails deterministically at model build — the e2e fixture for
    error-path drills (restart budget, errored-trace retention under
    tail sampling). `crash_message` hparam names the raise."""

    def build_model(self, mesh):
        raise RuntimeError(
            str(self.hparams.get("crash_message", "CrashingTrial: boom"))
        )


class LearnableTrial(SyntheticTrial):
    """Deterministic learnable task (linear labels): loss actually falls,
    so HP-search e2e tests can distinguish good lrs from bad ones."""

    def _batches(self, seed: int) -> Iterator[Dict[str, Any]]:
        w = np.random.default_rng(1234).normal(size=(784, 10)).astype(np.float32)
        rng = np.random.default_rng(seed)
        b = int(self.hparams.get("batch_size", 16))
        while True:
            x = rng.normal(size=(b, 28, 28, 1)).astype(np.float32)
            y = np.argmax(x.reshape(b, -1) @ w, axis=-1).astype(np.int32)
            yield {"image": x, "label": y}
