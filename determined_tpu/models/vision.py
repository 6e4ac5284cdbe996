"""Vision models: MNIST MLP and CIFAR CNN.

Capability parity with the reference's ladder of examples
(`examples/tutorials/mnist_pytorch`, `examples/computer_vision/cifar10_*`,
`e2e_tests` fixtures): small models used by tutorials, e2e tests, and the
ASHA HP-search workloads. batch = {"image": f32 [B, H, W, C], "label": int32
[B]}.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from determined_tpu.models.base import Metrics, Model


def _xent_metrics(logits: jax.Array, labels: jax.Array) -> Tuple[jax.Array, Metrics]:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = lse - jnp.take_along_axis(logits, labels[:, None], axis=-1).squeeze(-1)
    loss = jnp.mean(nll)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "accuracy": acc}


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    in_dim: int = 784
    hidden: int = 256
    n_classes: int = 10
    dtype: Any = jnp.float32


class MnistMLP(Model):
    def __init__(self, config: MLPConfig = MLPConfig(), mesh=None) -> None:
        self.config = config
        self.mesh = mesh  # unused; models replicate fine at this size

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        c = self.config
        k1, k2 = jax.random.split(rng)
        glorot = jax.nn.initializers.glorot_normal()
        return {
            "w1": glorot(k1, (c.in_dim, c.hidden), c.dtype),
            "b1": jnp.zeros((c.hidden,), c.dtype),
            "w2": glorot(k2, (c.hidden, c.n_classes), c.dtype),
            "b2": jnp.zeros((c.n_classes,), c.dtype),
        }

    def logical_axes(self) -> Dict[str, Tuple]:
        return {
            "w1": ("embed", "mlp"),
            "b1": ("mlp",),
            "w2": ("mlp", None),
            "b2": (None,),
        }

    def apply(self, params: Dict[str, jax.Array], images: jax.Array) -> jax.Array:
        x = images.reshape(images.shape[0], -1)
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    def loss(self, params, batch, rng) -> Tuple[jax.Array, Metrics]:
        del rng
        return _xent_metrics(self.apply(params, batch["image"]), batch["label"])


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    in_channels: int = 3
    channels: Tuple[int, ...] = (32, 64)
    hidden: int = 128
    n_classes: int = 10
    dtype: Any = jnp.float32


class CifarCNN(Model):
    """Conv stack via lax.conv_general_dilated (NHWC, MXU-friendly layouts)."""

    input_contract = (32, 32, 3)

    def __init__(self, config: CNNConfig = CNNConfig(), mesh=None) -> None:
        self.config = config
        self.mesh = mesh

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        c = self.config
        keys = jax.random.split(rng, len(c.channels) + 2)
        glorot = jax.nn.initializers.glorot_normal()
        params: Dict[str, Any] = {}
        cin = c.in_channels
        for i, cout in enumerate(c.channels):
            params[f"conv{i}"] = {
                "w": glorot(keys[i], (3, 3, cin, cout), c.dtype),
                "b": jnp.zeros((cout,), c.dtype),
            }
            cin = cout
        # Two 2x2 pools per conv halve H/W; flatten size depends on input.
        params["dense"] = {
            "w": None,  # lazily shaped at first apply via init_with_shape
            "b": jnp.zeros((c.hidden,), c.dtype),
        }
        params["out"] = {
            "w": glorot(keys[-1], (c.hidden, c.n_classes), c.dtype),
            "b": jnp.zeros((c.n_classes,), c.dtype),
        }
        # Resolve the lazy dense weight for the canonical 32x32 CIFAR input.
        hw = 32 // (2 ** len(c.channels))
        flat = hw * hw * c.channels[-1]
        params["dense"]["w"] = glorot(keys[-2], (flat, c.hidden), c.dtype)
        return params

    def logical_axes(self) -> Dict[str, Any]:
        c = self.config
        axes: Dict[str, Any] = {
            f"conv{i}": {"w": (None, None, None, "mlp"), "b": ("mlp",)}
            for i in range(len(c.channels))
        }
        axes["dense"] = {"w": ("embed", "mlp"), "b": ("mlp",)}
        axes["out"] = {"w": ("mlp", None), "b": (None,)}
        return axes

    def apply(self, params: Dict[str, Any], images: jax.Array) -> jax.Array:
        c = self.config
        x = images.astype(c.dtype)
        for i in range(len(c.channels)):
            p = params[f"conv{i}"]
            x = lax.conv_general_dilated(
                x, p["w"], window_strides=(1, 1), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + p["b"]
            x = jax.nn.relu(x)
            x = lax.reduce_window(
                x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ params["dense"]["w"] + params["dense"]["b"])
        return x @ params["out"]["w"] + params["out"]["b"]

    def loss(self, params, batch, rng) -> Tuple[jax.Array, Metrics]:
        del rng
        return _xent_metrics(self.apply(params, batch["image"]), batch["label"])
