"""Per cent of the traced window the device spent under the train step's
`optimizer` scope: the optax update, the parameter update, the global
norm and the sentinel's guarded update
(`benchmark/scope_reduce.py`; mean over the devices used; collectives
are in no scope). `None` where the program opens no scope."""
from benchmark import scope_reduce

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return scope_reduce.scope_share(run, "optimizer")
