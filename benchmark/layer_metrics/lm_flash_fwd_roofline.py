"""The flash-attention forward kernel's share of its roofline in a
`train_lm` cell: the least time the chip could take for every forward
call seen in the trace (`flops_bytes.flash_forward` at the configuration's query
heads, sequence and head width: the blocked kernels at 16 x 8192 x 256,
K/V repeated to the query heads) over the time those calls took
(`scope_reduce`'s `flash_s`). Calls are counted as attention layers x
the steps traced, not as kernel events: past `_FUSED_BWD_PARTIALS_CAP` a
backward is two kernels (dq, dk/dv), which `kernels/flash_backward.json`
would count as two backwards. `None` where no flash kernel ran."""
from benchmark import flops_bytes, lm_scope_reduce, scope_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    r = scope_reduce.for_run(run)
    if r is None or run.records["kind"] != "train":
        return None
    measured = r["flash_s"]["fwd"]
    steps = lm_scope_reduce.steps_traced(run, r["window_s"])
    if measured <= 0 or steps is None:
        return None
    c = run.config
    rows = int(run.traffic["global_batch"]) // run.chips
    seq = int(run.traffic["seq_len"])
    least = flops_bytes.roofline_seconds(*flops_bytes.flash_forward(
        rows, int(c["num_attention_heads"]), seq, seq, int(c["head_dim"])),
        run.peaks)[0]
    layers = int(c["num_hidden_layers"]) // int(c["full_attention_interval"])
    return 100.0 * least * layers * steps / measured
