"""Share of the traced window in which a flash-attention kernel ran on
the device (mean over the devices used)."""
from benchmark import kernel_events as kernels

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
KERNELS = ("flash_forward", "flash_backward", "flash_sharded")


def read(run):
    if run.trace is None or run.records["kind"] != "train":
        return None
    sec = sum(kernels.seconds_and_calls(run.trace, k)[0] for k in KERNELS)
    return 100.0 * sec / run.trace["window_s"] if sec > 0 else None
