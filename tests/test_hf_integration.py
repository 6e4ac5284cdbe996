"""HF integration: Flax GPT-2 as a platform trial (tiny config, offline)."""
import jax
import pytest

from determined_tpu import core
from determined_tpu.trainer import Batch, Trainer

transformers = pytest.importorskip("transformers")

TINY = {
    "hf_model_type": "gpt2",
    "hf_config": {
        "n_layer": 2, "n_head": 2, "n_embd": 64, "n_positions": 64,
        "vocab_size": 128,
    },
    "batch_size": 8,
    "seq_len": 32,
    "lr": 3e-3,
}


class TestHFTrial:
    def test_model_structure(self):
        from determined_tpu.integrations.hf import HFFlaxModel

        model = HFFlaxModel("gpt2", TINY["hf_config"])
        params = model.init(jax.random.PRNGKey(0))
        axes = model.logical_axes()
        assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
            axes, is_leaf=lambda x: isinstance(x, tuple)
        )
        logits = model.apply(params, jax.numpy.zeros((2, 16), jax.numpy.int32))
        assert logits.shape == (2, 16, 128)

    def test_trains_under_trainer(self, tmp_path):
        import numpy as np

        from determined_tpu.integrations.hf import HFTrial

        class MemorizableHFTrial(HFTrial):
            # One fixed structured batch: loss must fall well below the
            # uniform-entropy floor ln(vocab).
            def build_training_data(self):
                base = np.tile(np.arange(32), 8).reshape(8, 32).astype(np.int32)
                while True:
                    yield {"tokens": base}

            def build_validation_data(self):
                base = np.tile(np.arange(32), 8).reshape(8, 32).astype(np.int32)
                return [{"tokens": base}]

        ctx = core._context._dummy_init(checkpoint_storage=str(tmp_path))
        trainer = Trainer(MemorizableHFTrial(TINY), ctx)
        metrics = trainer.fit(max_length=Batch(25), report_period=Batch(5))
        assert trainer.steps_completed == 25
        assert metrics["loss"] < 1.0, f"should memorize, got {metrics['loss']}"


TINY_BERT = {
    "hf_model_type": "bert",
    "hf_config": {
        "num_hidden_layers": 2, "num_attention_heads": 2,
        "hidden_size": 64, "intermediate_size": 128,
        "max_position_embeddings": 64, "vocab_size": 128,
    },
    "num_labels": 2,
    "batch_size": 16,
    "seq_len": 32,
    "lr": 3e-3,
}


class TestHFClassifier:
    """The BERT-fine-tune rung of the platform ladder
    (`integrations/hf.py`)."""

    def test_model_structure(self):
        from determined_tpu.integrations.hf import HFFlaxClassifier

        model = HFFlaxClassifier("bert", TINY_BERT["hf_config"], num_labels=3)
        params = model.init(jax.random.PRNGKey(0))
        axes = model.logical_axes()
        assert jax.tree_util.tree_structure(
            params
        ) == jax.tree_util.tree_structure(
            axes, is_leaf=lambda x: isinstance(x, tuple)
        )
        logits = model.apply(
            params, jax.numpy.zeros((2, 16), jax.numpy.int32)
        )
        assert logits.shape == (2, 3)

    def test_finetune_learns_separable_stream(self, tmp_path):
        from determined_tpu.integrations.hf import HFClassifierTrial

        ctx = core._context._dummy_init(checkpoint_storage=str(tmp_path))
        trial = HFClassifierTrial(TINY_BERT)
        trainer = Trainer(trial, ctx)
        trainer.fit(max_length=Batch(30), report_period=Batch(10))
        assert trainer.steps_completed == 30
        model = trial.build_model(None)
        batch = next(iter(trial.build_validation_data()))
        metrics = jax.jit(model.eval_metrics)(
            trainer.state["params"], batch
        )
        # the class is literally written into token 0: must beat chance
        assert float(metrics["accuracy"]) > 0.7
