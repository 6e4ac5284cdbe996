"""The grouped matmuls' share of their roofline in a latent-attention
cell (experts of width 1536): the least time the chip could take for the
rows ACTUALLY routed to the experts held in the steps traced
(`mla_flops_bytes.experts_step`, by the program's counter
`moe_held_rows_per_token`, every expert layer and the MTP module's) over
the time under the `moe_experts` scope (`lm_scope_reduce`: forward, the
forward repeated in the backward, and the backward). `None` where the
program reports no such counter, opens no such scope, or the
configuration is of another family."""
from benchmark import lm_scope_reduce, mla_flops_bytes

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    rows = run.records.get("counters", {}).get("moe_held_rows_per_token")
    if rows is None or "kv_lora_rank" not in run.config:
        return None
    tokens = int(run.traffic["global_batch"]) * int(run.traffic["seq_len"])
    return lm_scope_reduce.roofline_share(
        run, "moe_experts",
        mla_flops_bytes.experts_step(run.config, tokens // run.chips, rows))
