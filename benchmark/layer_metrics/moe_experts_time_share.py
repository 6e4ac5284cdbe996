"""Per cent of the traced window the device spent under `moe_experts`: the
grouped matmuls over the experts held and the activation between them,
forward, backward and recomputed (`benchmark/lm_scope_reduce.py`).
`None` where the program opens no such scope."""
from benchmark import lm_scope_reduce

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return lm_scope_reduce.inner_share(run, "moe_experts")
