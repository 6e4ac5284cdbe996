"""The flash kernels' share of their roofline in a train step: the
least time the chip could take for every forward and backward call seen
in the trace (`flops_bytes`, per device: its share of the batch, all
heads) over the time those kernels took. Compute-bound at these shapes
(1024 x 1024 x 64 a head). On a mesh of several chips the trace names
forward and backward alike (`kernels/flash_sharded.json`); one of each
runs a layer a step, so half of those calls are taken as forwards."""
from benchmark import flops_bytes
from benchmark import kernel_events as kernels

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or run.records["kind"] != "train":
        return None
    m = flops_bytes.dims(run.config)
    rows = int(run.traffic["global_batch"]) // run.chips
    seq = int(run.traffic["seq_len"])
    shape = (rows, m["heads"], seq, seq, m["head_dim"])
    least = {
        "flash_forward": flops_bytes.roofline_seconds(
            *flops_bytes.flash_forward(*shape), run.peaks)[0],
        "flash_backward": flops_bytes.roofline_seconds(
            *flops_bytes.flash_backward(*shape), run.peaks)[0],
    }
    least["flash_sharded"] = (
        least["flash_forward"] + least["flash_backward"]) / 2
    needed = measured = 0.0
    for kernel, per_call in least.items():
        sec, calls = kernels.seconds_and_calls(run.trace, kernel)
        needed += per_call * calls
        measured += sec
    return 100.0 * needed / measured if measured > 0 else None
