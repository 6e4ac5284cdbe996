"""From a configuration file (the public config.json's keys) to the
program's own model: the one place that knows both vocabularies."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict


def gpt_config_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """`GPTConfig` keyword arguments for a configuration, leaving out
    every one that equals the dataclass's default: GPT-2 small then
    builds through `get_model("gpt2-small")` with no override at all,
    which is the path users of the registry take."""
    from determined_tpu.models.gpt import GPTConfig

    if config.get("model_type") != "gpt2":
        raise ValueError(
            f"model_type {config.get('model_type')!r}: the program trains "
            "and serves GPT-2's block only")
    if config.get("activation_function") != "gelu_new" \
            or config.get("layer_norm_epsilon") != 1e-5 \
            or not config.get("tie_word_embeddings", True):
        raise ValueError("models/gpt.py hard-codes gelu_new, eps 1e-5 and "
                         "a tied head; this configuration differs")
    d = int(config["n_embd"])
    want = {
        "vocab_size": int(config.get("padded_vocab_size")
                          or config["vocab_size"]),
        "n_layers": int(config["n_layer"]),
        "n_heads": int(config["n_head"]),
        "d_model": d,
        "d_ff": int(config["n_inner"] or 4 * d),
        "seq_len": int(config["n_positions"]),
    }
    # Rehearsals on the CPU pass program-side overrides (remat off, ...).
    want.update(config.get("gpt_config_overrides", {}))
    defaults = {f.name: f.default for f in dataclasses.fields(GPTConfig)}
    return {k: v for k, v in want.items() if defaults.get(k) != v}
