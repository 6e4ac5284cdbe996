"""Driver of `"kind": "train_lm_models"` mixes: `drivers/train_lm.py`'s
run, stamp for stamp, for any architecture listed in `lm_models.json`.

`train_lm.py` cannot be edited by the PR that added this file, and three
of its module-level names stop it short of a second architecture:
`MODELS` (Qwen3-Next alone), `COUNTERS` (no `mtp_loss`) and
`trial_hparams` (no learning-rate warm-up). `as_train_lm()` puts this
module's in their place for the length of a call and takes them out
again; `run` is then `train_lm.run` itself: the same context, searcher,
windows, records and `correct` (the first loss AND every leaf's gradient
against the float32 reference at the timed sizes, outside the window).
The tools that call `train_lm`'s functions (`tools/size_train_lm.py`,
`tools/lm_control.py`) run under the same switch:
`python -m benchmark.tools.lm_models <tool> ...`.

Traffic file: `train_lm.py`'s keys, and `lr_warmup_steps` (steps of the
trial's linear ramp from 0 to `lr`; 0 or absent: none).
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
from typing import Any, Dict
from unittest import mock

from benchmark.drivers import train_lm

@functools.lru_cache(maxsize=1)
def models() -> Dict[str, Any]:
    """model_type -> (registry name, the reference's module), as
    `train_lm.MODELS` holds them: `lm_models.json`."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "lm_models.json")) as f:
        return {k: (v["registry"], v["reference"])
                for k, v in json.load(f)["models"].items()}


#: kept from every training report; the first is `held_rows_by_report`'s
COUNTERS = (*train_lm.COUNTERS, "mtp_loss")


_trial_hparams = train_lm.trial_hparams     # (its own, before any switch)


def trial_hparams(config: Dict[str, Any], traffic: Dict[str, Any],
                  global_batch: int) -> Dict[str, Any]:
    return {**_trial_hparams(config, traffic, global_batch),
            "lr_warmup_steps": int(traffic.get("lr_warmup_steps", 0))}


@contextlib.contextmanager
def as_train_lm():
    """`train_lm`'s functions read this module's table, counters and
    hyperparameters while the block runs."""
    with mock.patch.multiple(train_lm, MODELS=models(), COUNTERS=COUNTERS,
                             trial_hparams=trial_hparams):
        yield


def run(h) -> Dict[str, Any]:
    with as_train_lm():
        return train_lm.run(h)
