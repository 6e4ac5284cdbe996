"""Driver of `"kind": "train_lm_models"` mixes: `drivers/train_lm.py`'s
run, stamp for stamp, for any architecture listed in `lm_models.json`.

`train_lm.py` cannot be edited by the PR that added this file, and two
of its module-level names stop it short of a second architecture:
`MODELS` (Qwen3-Next alone) and `COUNTERS` (no `mtp_loss`).
`as_train_lm()` puts this module's in their place for the length of a
call and takes them out again; `run` is then `train_lm.run` itself: the
same context, searcher, windows, records and `correct` (the first loss
AND every leaf's gradient against the float32 reference at the timed
sizes, outside the window).
The tools that call `train_lm`'s functions (`tools/size_train_lm.py`,
`tools/lm_control.py`) run under the same switch:
`python -m benchmark.tools.lm_models <tool> ...`.

Traffic file: `train_lm.py`'s keys.
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


@contextlib.contextmanager
def as_train_lm():
    """`train_lm`'s functions read this module's table and counters
    while the block runs."""
    with mock.patch.multiple(train_lm, MODELS=models(), COUNTERS=COUNTERS):
        yield


def run(h) -> Dict[str, Any]:
    with as_train_lm():
        return train_lm.run(h)
