"""Model zoo: flagship GPT plus the example-ladder models.

Registry mirrors the role of the reference's `examples/` + `model_hub/`
catalog: named recipes the platform's configs can reference by string
(experiment config `model.name`).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from determined_tpu.models import gpt as gpt_mod
from determined_tpu.models.attention import attention
from determined_tpu.models.base import Model
from determined_tpu.models.gpt import GPT, GPTConfig
from determined_tpu.models.glm4_moe_lite import Glm4MoeLite, Glm4MoeLiteConfig
from determined_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
from determined_tpu.models.generative import DCGAN, DDPM, DDPMConfig, GANConfig
from determined_tpu.models.vision import CifarCNN, CNNConfig, MLPConfig, MnistMLP

_REGISTRY: Dict[str, Callable[..., Model]] = {
    "ddpm": lambda mesh=None, **kw: DDPM(
        DDPMConfig(**kw) if kw else DDPMConfig(), mesh=mesh
    ),
    "dcgan": lambda mesh=None, **kw: DCGAN(
        GANConfig(**kw) if kw else GANConfig(), mesh=mesh
    ),
    "gpt2-small": lambda mesh=None, **kw: GPT(
        gpt_mod.small() if not kw else GPTConfig(**kw), mesh=mesh
    ),
    "gpt2-medium": lambda mesh=None, **kw: GPT(
        gpt_mod.medium() if not kw else GPTConfig(**kw), mesh=mesh
    ),
    "gpt-tiny": lambda mesh=None, **kw: GPT(gpt_mod.tiny(**kw), mesh=mesh),
    # built from the public config.json's keys (docs/dtrain.md)
    "qwen3-next": lambda mesh=None, **kw: Qwen3Next(
        Qwen3NextConfig.from_keys(kw), mesh=mesh
    ),
    "glm4-moe-lite": lambda mesh=None, **kw: Glm4MoeLite(
        Glm4MoeLiteConfig.from_keys(kw), mesh=mesh
    ),
    "mnist-mlp": lambda mesh=None, **kw: MnistMLP(
        MLPConfig(**kw) if kw else MLPConfig(), mesh=mesh
    ),
    "cifar-cnn": lambda mesh=None, **kw: CifarCNN(
        CNNConfig(**kw) if kw else CNNConfig(), mesh=mesh
    ),
}


def get_model(name: str, mesh: Optional[Any] = None, **hparams: Any) -> Model:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](mesh=mesh, **hparams)


__all__ = [
    "Model",
    "GPT",
    "GPTConfig",
    "Qwen3Next",
    "Qwen3NextConfig",
    "Glm4MoeLite",
    "Glm4MoeLiteConfig",
    "MnistMLP",
    "CifarCNN",
    "DDPM",
    "DCGAN",
    "attention",
    "get_model",
]
