"""Per cent of the traced window the device spent under `gdn`: the whole
gated-delta mixer (projections, convolution, the chunked rule, the gated
norm), forward, backward and recomputed (`benchmark/lm_scope_reduce.py`).
`None` where the program opens no such scope."""
from benchmark import lm_scope_reduce

LAYER = "model step"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return lm_scope_reduce.inner_share(run, "gdn")
