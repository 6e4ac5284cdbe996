"""Per cent of the traced window the device spent in operations that
are not collectives and lie under none of the train step's scopes: what
the compiler left without a name stack (layout copies, waits on its own
asynchronous copies, fusions whose metadata it dropped) and whatever the
program runs outside a scope: today the layout copies between
attention's projections and its kernels, which no scope may reach
(`benchmark/scope_reduce.py`; mean over the devices used). Larger than
those: a scope is missing. `None` where the program
opens no scope."""
from benchmark import scope_reduce

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return scope_reduce.scope_share(run, scope_reduce.UNSCOPED)
