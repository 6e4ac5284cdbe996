"""The grouped matmuls' share of their roofline: the least time the chip
could take for the rows ACTUALLY routed to the experts held in the steps
traced (`lm_flops_bytes.experts_step`, by the program's counter
`moe_held_rows_per_token`) over the time under the `moe_experts` scope
(forward, the forward repeated in the backward, and the backward).
`None` where the program reports no such counter or opens no such scope."""
from benchmark import lm_flops_bytes, lm_scope_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    rows = run.records.get("counters", {}).get("moe_held_rows_per_token")
    if rows is None:
        return None
    tokens = int(run.traffic["global_batch"]) * int(run.traffic["seq_len"])
    return lm_scope_reduce.roofline_share(
        run, "moe_experts",
        lm_flops_bytes.experts_step(run.config, tokens // run.chips, rows))
