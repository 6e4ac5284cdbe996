"""Per cent of the traced window the device spent under the train step's
`attn` scope: the attention half of every block, forward, backward and
recomputed. The scope covers the projections; the flash kernels carry
no scope and are counted here all the same (`scopes.json`, `flash`); the
layout copies between the two are `unscoped`
(`benchmark/scope_reduce.py`; mean over the devices used; collectives
are in no scope). `None` where the program opens no scope."""
from benchmark import scope_reduce

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return scope_reduce.scope_share(run, "attn")
