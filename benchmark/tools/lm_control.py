"""The two readings behind a `"kind": "train_lm"` cell's limits, on the
chip at the timed sizes: the program's and the CONTROL's, each through
the cell's own `check` against the float32 reference.

    python -m benchmark.tools.lm_control \\
        --workload qwen3next-train-8k-ep16share --seeds 11,3000000001

The control is the reference's own equations with the operands of every
product rounded to 3 mantissa bits (float8 e4m3's: the nearest format
below the bf16 operands the configuration states). `check` must refuse
it and pass the program; `PERF.md` has both readings, and
the limit lies between them. No run of the cell computes the control:
it decides nothing there. Off the chip (`tests/test_qwen3_next.py`, a
tiny size) the same function holds the same verdicts.
"""
from __future__ import annotations

import argparse
import importlib
import json
import time
from typing import Any, Dict

import jax

from benchmark.drivers import train_lm


def readings(config: Dict[str, Any], traffic: Dict[str, Any],
             seed: int) -> Dict[str, Any]:
    """{"program": check, "control": check} on the batch `seed` draws
    first and the parameters `weights_seed` draws, as the cell's run."""
    from determined_tpu.exec.builtin_trials import SyntheticTrial
    from determined_tpu.models import get_model

    name, model_kw, reference = train_lm.model_of(config)
    reference = importlib.import_module(reference)
    trial = SyntheticTrial(train_lm.trial_hparams(
        config, traffic, int(traffic["global_batch"])))
    tokens = next(trial._batches(seed))["tokens"]
    model = get_model(name, **model_kw)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(int(traffic["weights_seed"])))
    stamps = [time.perf_counter()]
    loss, grads = jax.device_get(jax.jit(jax.value_and_grad(
        lambda p, t: model.loss(p, {"tokens": t}, None)[0]))(params, tokens))
    stamps.append(time.perf_counter())
    params = jax.device_get(params)
    ref_loss, ref_grads = reference.loss_and_gradient(params, tokens, config)
    stamps.append(time.perf_counter())
    program = reference.check(
        float(loss), ref_loss, reference.gradient_gaps(grads, ref_grads))
    loss, low_grads = reference.loss_and_gradient(
        params, tokens, config, operand_mantissa=3)
    stamps.append(time.perf_counter())
    control = reference.check(
        loss, ref_loss, reference.gradient_gaps(low_grads, ref_grads))
    return {"seed": seed, "program": program, "control": control,
            "seconds": dict(zip(("program", "reference", "control"), (
                round(b - a, 2) for a, b in zip(stamps, stamps[1:])))),
            "memory_stats": {k: v for k, v in (
                jax.devices()[0].memory_stats() or {}).items()
                if k.startswith("peak")}}


def main() -> int:
    from benchmark.run import Cell
    from determined_tpu.common import compile_cache

    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    args = parser.parse_args()
    cell = Cell(args.workload)
    compile_cache.enable()
    for seed in args.seeds.split(","):
        print(json.dumps(readings(cell.config, cell.traffic, int(seed))),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
