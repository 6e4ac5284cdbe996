"""Per cent of the traced window the device spent under `mla`: the latent
projections on either side of the attention call, their norms, rotary
and the concatenations, forward, backward and recomputed
(`benchmark/mla_scope_reduce.py`); the flash kernels carry no scope and
are `flash_time_share`'s. `None` where the program opens no such scope."""
from benchmark import mla_scope_reduce

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return mla_scope_reduce.inner_share(run, "mla")
