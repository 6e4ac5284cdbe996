"""Per cent of the traced window the device spent under `mtp`: the
multi-token-prediction module's input projection, its block (but its
flash kernels, which carry no scope), its head pass and its loss term,
forward, backward and recomputed (`benchmark/mla_scope_reduce.py`).
`None` where the configuration has no module or the program opens no
such scope."""
from benchmark import mla_scope_reduce

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    if not run.config.get("num_nextn_predict_layers"):
        return None
    return mla_scope_reduce.inner_share(run, "mtp")
