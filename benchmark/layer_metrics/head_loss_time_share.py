"""Per cent of the traced window the device spent under the train step's
`head_loss` scope: the final layer norm, the output head's matmul and
the loss arithmetic after it, forward and backward
(`benchmark/scope_reduce.py`; mean over the devices used; collectives
are in no scope). `None` where the program opens no scope."""
from benchmark import scope_reduce

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return scope_reduce.scope_share(run, "head_loss")
