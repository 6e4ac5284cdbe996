"""The flash-attention backward kernel's share of its roofline in a
latent-attention cell: the blocked kernels at 20 x 8192 x 256, every
attention layer's call (the MTP module's block included) in the steps
traced (`benchmark/mla_scope_reduce.py::flash_roofline`). `None` where
no flash kernel ran or the configuration is of another family."""
from benchmark import mla_scope_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return mla_scope_reduce.flash_roofline(run, "bwd")
