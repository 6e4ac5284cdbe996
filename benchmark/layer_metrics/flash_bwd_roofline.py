"""The flash-attention backward kernel's share of its roofline in a
train step: the least time the chip could take for every backward call
seen in the trace (`flops_bytes.flash_backward`: dq, dk and dv in one
kernel) over the time those calls took. Which calls are backwards:
`scope_reduce.pass_of`. `None` where no flash kernel ran."""
from benchmark import flops_bytes
from benchmark.layer_metrics import flash_fwd_roofline

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return flash_fwd_roofline.share(run, flops_bytes.flash_backward, "bwd")
