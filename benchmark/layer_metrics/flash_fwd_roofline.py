"""The flash-attention forward kernel's share of its roofline in a train
step: the least time the chip could take for every forward call seen in
the trace (`flops_bytes.flash_forward`, per device: its share of the
batch, all heads; compute-bound at 1024 x 1024 x 64 a head) over the
time those calls took. Which calls are forwards: `scope_reduce.pass_of`
(by name on one chip; on a mesh, where forward and backward share the
name `shard_map`, by the name stack). Calls that remat repeats count as
calls: the kernel ran them. `None` where no flash kernel ran."""
from benchmark import flops_bytes
from benchmark import scope_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def share(run, needs, which):
    """100 x (the least seconds `needs` gives a call x calls seen) /
    (seconds of those calls), for the flash kernels of pass `which`."""
    r = scope_reduce.for_run(run)
    if r is None or run.records["kind"] != "train":
        return None
    m = flops_bytes.dims(run.config)
    rows = int(run.traffic["global_batch"]) // run.chips
    seq = int(run.traffic["seq_len"])
    least = flops_bytes.roofline_seconds(
        *needs(rows, m["heads"], seq, seq, m["head_dim"]), run.peaks)[0]
    measured = r["flash_s"][which]
    if measured <= 0:
        return None
    return 100.0 * least * r["flash_calls"][which] / measured


def read(run):
    return share(run, flops_bytes.flash_forward, "fwd")
